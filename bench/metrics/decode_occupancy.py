"""Share of the decode batch's lanes that carried a request: rows of every
traced ``serve.decode`` span over spans times the engine's slots."""
from bench import spans as sp


def read(run):
    dec = sp.named(sp.of_run(run), "serve.decode")
    if not dec:
        return None
    rows = sum(s[3]["rows"] for s in dec)
    return 100.0 * rows / (len(dec) * run["engine"]["slots"])

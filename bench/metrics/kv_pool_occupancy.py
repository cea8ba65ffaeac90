"""Share of the KV pool's reserved rows that hold a cache row: over every
traced ``serve.decode`` span, the rows the occupied slots hold
(``held_tokens``) over the pages reserved for them (``pages``) times the
page length.  A request reserves its whole prompt and output at admission,
so the share is what that reservation leaves unused; pages shared by a
prefix hit count once, so shared prefixes can read above 100."""
from bench import spans as sp


def read(run):
    dec = [s for s in sp.named(sp.of_run(run), "serve.decode")
           if "pages" in s[3]]
    reserved = sum(s[3]["pages"] for s in dec) * run["engine"]["page_len"]
    if not reserved:
        return None
    return 100.0 * sum(s[3]["held_tokens"] for s in dec) / reserved

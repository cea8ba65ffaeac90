"""Restart smoke: SIGKILL a journaled HTTP serving process mid-stream,
restart it with ``--recover``, and assert the resumed streams are BITWISE
the uninterrupted control run (the CI ``restart-smoke`` leg).

This is the end-to-end proof of the crash-safe serving claim, driven over
real process boundaries rather than in-process fault injection:

1. a CONTROL server runs two requests to completion and records their
   full token streams (greedy decode makes them the deterministic oracle);
2. a VICTIM server with ``--journal-dir`` gets the same two requests,
   and the moment each stream has produced a few tokens the process is
   SIGKILLed — no atexit, no flush, exactly what a crash looks like;
3. a RECOVERY server starts over the same journal with ``--recover``;
   the client re-attaches at ``GET /resume/{uid}`` and reads each full
   stream (replayed prefix + live continuation);
4. the resumed streams must equal the control streams token-for-token,
   and the recovery server must report journal recovery on stdout.

Tokens the victim emitted after the journal's last committed fsync are
allowed to be lost on disk — recovery re-derives them bitwise (greedy
decode), which is exactly why the assertion is on the FULL stream, not on
what the journal happened to hold.

This is a CPU tool: it runs three serving processes (JAX_PLATFORMS=cpu,
the smoke-sized model), and a TPU belongs to one process at a time.

Run locally:  PYTHONPATH=src python tools/restart_smoke.py
"""
import os
import pathlib
import signal
import subprocess
import sys
import tempfile
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = pathlib.Path(__file__).resolve().parent.parent
SRC = str(REPO / "src")
sys.path.insert(0, SRC)

from repro.serving.server import (get_json, resume_stream,  # noqa: E402
                                  stream_generate)

ARCH = "gemma2-2b"
NEW_TOKENS = 12
PROMPTS = {7: [1, 2, 3, 4], 8: [5, 6, 7]}


def _spawn(extra, port_file_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "repro.launch.serve", "--arch", ARCH,
           "--smoke", "--batch", "2", "--max-len", "64",
           "--http-port", "0"] + extra
    return subprocess.Popen(cmd, env=env, cwd=str(REPO),
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def _wait_port(proc, timeout=240.0):
    """Parse the bound ephemeral port off the serve banner."""
    deadline = time.time() + timeout
    buf = []
    while time.time() < deadline:
        if proc.poll() is not None:
            raise RuntimeError("serve process died during startup:\n"
                               + "".join(buf))
        line = proc.stdout.readline()
        if not line:
            time.sleep(0.05)
            continue
        buf.append(line)
        if "HTTP front-end on http://127.0.0.1:" in line:
            port = int(line.split("http://127.0.0.1:", 1)[1].split()[0])
            return port, buf
    raise RuntimeError("serve process never bound a port:\n" + "".join(buf))


def _wait_ready(port, timeout=120.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            code, body = get_json("127.0.0.1", port, "/readyz", timeout=5.0)
            if code == 200:
                return body
        except OSError:
            pass
        time.sleep(0.1)
    raise RuntimeError(f"/readyz never went 200 on port {port}")


def main() -> int:
    with tempfile.TemporaryDirectory() as tmp:
        journal = os.path.join(tmp, "journal")

        # -- 1. control run: the uninterrupted oracle streams -----------
        ctrl = _spawn([])
        try:
            port, _ = _wait_port(ctrl)
            _wait_ready(port)
            oracle = {}
            for uid, prompt in PROMPTS.items():
                frames = list(stream_generate(
                    "127.0.0.1", port, prompt, uid=uid,
                    max_new_tokens=NEW_TOKENS))
                assert frames[-1]["type"] == "done", frames[-1]
                oracle[uid] = [f["token"] for f in frames
                               if f["type"] == "token"]
                assert len(oracle[uid]) == NEW_TOKENS
        finally:
            ctrl.kill()
            ctrl.wait()
        print(f"[restart-smoke] control streams recorded: "
              f"{ {u: len(t) for u, t in oracle.items()} }")

        # -- 2. victim: journaled, SIGKILLed mid-stream ------------------
        victim = _spawn(["--journal-dir", journal,
                         "--journal-sync", "always"])
        try:
            port, _ = _wait_port(victim)
            _wait_ready(port)
            # read a few tokens from each stream concurrently-ish: start
            # both, pull ~3 frames from each, then SIGKILL with both
            # requests mid-decode
            gens = {uid: stream_generate("127.0.0.1", port, prompt,
                                         uid=uid, max_new_tokens=NEW_TOKENS)
                    for uid, prompt in PROMPTS.items()}
            seen: dict = {uid: [] for uid in PROMPTS}
            for uid, gen in gens.items():
                for frame in gen:
                    if frame["type"] == "token":
                        seen[uid].append(frame["token"])
                        if len(seen[uid]) >= 3:
                            break
                    elif frame["type"] in ("done", "error"):
                        raise AssertionError(
                            f"victim stream {uid} terminated before the "
                            f"kill: {frame}")
            assert all(len(t) >= 3 for t in seen.values()), seen
            os.kill(victim.pid, signal.SIGKILL)
        finally:
            victim.kill()
            victim.wait()
        for uid, toks in seen.items():
            assert toks == oracle[uid][:len(toks)], \
                f"pre-kill stream {uid} diverged: {toks} vs {oracle[uid]}"
        print(f"[restart-smoke] victim SIGKILLed mid-stream with "
              f"{ {u: len(t) for u, t in seen.items()} } tokens out")

        # -- 3. recovery: restart over the journal, re-attach ------------
        rec = _spawn(["--journal-dir", journal, "--journal-sync", "always",
                      "--recover"])
        try:
            port, banner = _wait_port(rec)
            assert any("journal recovery" in ln for ln in banner), banner
            _wait_ready(port)
            for uid, want in oracle.items():
                frames = list(resume_stream("127.0.0.1", port, uid))
                toks = [f["token"] for f in frames
                        if f["type"] == "token"]
                assert frames and frames[-1]["type"] == "done", \
                    (uid, frames[-2:])
                assert toks == want, (
                    f"resumed stream {uid} NOT bitwise the control: "
                    f"{toks} vs {want}")
                n_replayed = sum(1 for f in frames if f.get("replayed"))
                print(f"[restart-smoke] uid {uid}: {n_replayed} replayed "
                      f"+ {len(toks) - n_replayed} live tokens == control")
            # graceful exit exercises the SIGTERM drain path too
            rec.send_signal(signal.SIGTERM)
            try:
                rec.wait(timeout=60)
            except subprocess.TimeoutExpired:
                raise AssertionError("SIGTERM drain never exited")
        finally:
            rec.kill()
            rec.wait()
    print("[restart-smoke] OK: resumed streams bitwise the "
          "uninterrupted control")
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Measure what the program's weight quantizer makes of a Gaussian matrix.

The benchmark draws its packed weights directly (``bench/weights.py``), so
that a 14 B model never exists in f32.  For the drawn bytes to look like
packed Gaussian weights, three things are read off the repository's own
quantizer (``qtensor.quantize`` with 16x16 MixFP4 tiles) on a seeded
Gaussian matrix:

* the share of tiles that pick E1M2 (the type bit),
* for each tile type, the distribution of the 3-bit code magnitudes,
* for each tile type, the distribution of the E4M3 scale bytes.

Each distribution is written as a 128-entry table: entry ``i`` is the
value at quantile ``(i + 0.5) / 128``, so a uniform 7-bit draw indexes it.
Run on the CPU and paste the printed JSON into a configuration's
``"weights"`` section:

    PYTHONPATH=src JAX_PLATFORMS=cpu python3 bench/calibrate.py 5120 5120
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

TABLE = 128


def tables(payload: np.ndarray, scales: np.ndarray) -> dict:
    """Type share and quantile tables from one packed (K/2, N) payload
    and its (K/16, N/16) scale bytes."""
    k2, n = payload.shape
    nib = np.stack([payload & 0xF, payload >> 4], axis=1).reshape(2 * k2, n)
    mag = nib & 0x7
    tbit = scales >> 7
    t_full = np.repeat(np.repeat(tbit, 16, axis=0), 16, axis=1)
    q = (np.arange(TABLE) + 0.5) / TABLE
    out = {"e1m2_share": float(tbit.mean())}
    for t, name in ((0, "e2m1"), (1, "e1m2")):
        mags = np.sort(mag[t_full == t].ravel())
        sbytes = np.sort((scales[tbit == t] & 0x7F).ravel())
        out[f"{name}_magnitude"] = [int(mags[int(x * len(mags))]) for x in q]
        out[f"{name}_scale_byte"] = [int(sbytes[int(x * len(sbytes))])
                                     for x in q]
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    k, n = (int(a) for a in argv[:2]) if argv else (5120, 5120)
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
    import jax
    from repro.core import qtensor

    w = jax.random.normal(jax.random.key(0), (k, n), np.float32)
    qt = qtensor.quantize(
        w, qtensor.QuantSpec("mixfp4", qtensor.BlockLayout2D(16, 16)))
    print(json.dumps(tables(np.asarray(qt.payload), np.asarray(qt.scales))))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Packed weights drawn on the device from the seed.

A 14 B model does not fit one chip in f32, and quantizing it leaf by leaf
would take minutes of set-up in every run.  So each projection is drawn
directly in the MixFP4 wire format: 16x16 tiles whose type bit, E4M3 scale
byte and 3-bit code magnitudes follow the distributions that the
repository's own quantizer produces on a Gaussian matrix (measured by
``bench/calibrate.py`` and stored in the configuration's ``"weights"``
section).  The per-tensor ``scale32`` is set so that the dequantized
standard deviation is ``1/sqrt(K)``, the model's ``linear_init``.  A zero
magnitude never carries a sign, and a scale byte never has magnitude 0,
so neither ``0x80`` scale bytes nor negative-zero codes occur.

Everything is made by one jitted call.  The result is plain arrays; the
harness wraps the projections into the program's ``QTensor`` and the
plain reference dequantizes the same arrays with its own decoder.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

G = 16
E2M1_LEVELS = np.array([0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0])
E1M2_LEVELS = np.arange(8, dtype=np.float64)
EMBED_STD = 0.02  # the model's embedding init
PAD_VOCAB = 256   # the model pads its embedding rows to this multiple


def e4m3_value(byte: np.ndarray) -> np.ndarray:
    """Magnitude of an E4M3 byte (bits 6..0), as the format defines it."""
    b = np.asarray(byte, np.int64) & 0x7F
    e, m = b >> 3, b & 7
    return np.where(e > 0, 2.0 ** (e - 7) * (1 + m / 8), 2.0 ** -6 * m / 8)


def projections(arch: dict) -> dict:
    """(K, N) of each projection of one layer, by the program's leaf name."""
    d, dh = arch["d_model"], arch["head_dim"]
    out = {"wq": (d, arch["n_heads"] * dh),
           "wk": (d, arch["n_kv_heads"] * dh),
           "wv": (d, arch["n_kv_heads"] * dh),
           "wo": (arch["n_heads"] * dh, d),
           "w_up": (d, arch["d_ff"]),
           "w_down": (arch["d_ff"], d)}
    if arch["mlp_type"] in ("swiglu", "geglu"):
        out["w_gate"] = (d, arch["d_ff"])
    return out


def padded_vocab(vocab: int) -> int:
    return -(-vocab // PAD_VOCAB) * PAD_VOCAB


def _mean_square(tables: dict) -> float:
    """E[(code level x scale byte value)^2] of one drawn weight, before
    ``scale32``: codes and scale bytes are drawn independently per type."""
    share = tables["e1m2_share"]
    total = 0.0
    for name, levels, p in (("e2m1", E2M1_LEVELS, 1 - share),
                            ("e1m2", E1M2_LEVELS, share)):
        lev = levels[np.asarray(tables[f"{name}_magnitude"])]
        sc = e4m3_value(np.asarray(tables[f"{name}_scale_byte"]))
        total += p * np.mean(lev ** 2) * np.mean(sc ** 2)
    return float(total)


def _lookup(idx, table):
    """``table[idx]`` for a sorted 128-entry table, as a sum of compares
    (a gather from a small table is slow on the TPU's vector unit)."""
    table = [int(v) for v in table]
    out = jnp.full(idx.shape, table[0], jnp.int32)
    for i in range(1, len(table)):
        if table[i] != table[i - 1]:
            out = out + jnp.where(idx >= i, table[i] - table[i - 1], 0)
    return out


def _draw_matrix(key, k: int, n: int, tables: dict):
    """One (K, N) projection: payload (K/2, N) and scales (K/16, N/16)."""
    kt, ks, kc = jax.random.split(key, 3)
    tiles = (k // G, n // G)
    t = jax.random.uniform(kt, tiles) < tables["e1m2_share"]
    sidx = (jax.random.bits(ks, tiles, jnp.uint8) & 0x7F).astype(jnp.int32)
    sbyte = jnp.where(t, _lookup(sidx, tables["e1m2_scale_byte"]),
                      _lookup(sidx, tables["e2m1_scale_byte"]))
    scales = (sbyte | (t.astype(jnp.int32) << 7)).astype(jnp.uint8)
    cb = jax.random.bits(kc, (k // G, G, n // G, G), jnp.uint8)
    cidx = (cb & 0x7F).astype(jnp.int32)
    tt = t[:, None, :, None]
    mag = jnp.where(tt, _lookup(cidx, tables["e1m2_magnitude"]),
                    _lookup(cidx, tables["e2m1_magnitude"]))
    sign = jnp.where(mag > 0, (cb >> 7).astype(jnp.int32), 0)
    nib = (mag | (sign << 3)).reshape(k // 2, 2, n)
    payload = (nib[:, 0] | (nib[:, 1] << 4)).astype(jnp.uint8)
    return payload, scales


@functools.partial(jax.jit, static_argnums=(1,))
def _draw(key, spec):
    arch, tables = dict(spec[0]), {k: (list(v) if isinstance(v, tuple) else v)
                                   for k, v in spec[1]}
    L, d = arch["n_layers"], arch["d_model"]
    ms = _mean_square(tables)
    keys = jax.random.split(key, 16)
    proj = {}
    for i, (name, (k, n)) in enumerate(sorted(projections(arch).items())):
        lkeys = jax.random.split(keys[i], L)
        payload, scales = jax.lax.map(
            lambda kk: _draw_matrix(kk, k, n, tables), lkeys)
        s32 = jnp.full((L,), (1.0 / np.sqrt(k)) / np.sqrt(ms), jnp.float32)
        proj[name] = (payload, scales, s32)
    embed = jax.random.normal(
        keys[15], (padded_vocab(arch["vocab"]), d), jnp.float32) * EMBED_STD
    ones = jnp.ones((L, d), jnp.float32)
    return {"proj": proj, "embed": embed, "ln_attn": ones, "ln_mlp": ones,
            "ln_f": jnp.ones((d,), jnp.float32)}


def _freeze(d: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in d.items()))


def draw(key, arch: dict, tables: dict) -> dict:
    """All weights of one dense model, made on the device in one call:
    ``{"proj": {name: (payload, scales, scale32)}, "embed", "ln_attn",
    "ln_mlp", "ln_f"}``, every projection stacked over layers."""
    keep = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab", "mlp_type")
    return _draw(key, (_freeze({k: arch[k] for k in keep}), _freeze(tables)))

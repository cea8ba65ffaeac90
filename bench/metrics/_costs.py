"""Operations and bytes of the kernels and of the model, from shapes.

Counted from the algorithm, not from the compiled program: a GEMM needs
``2 m k n`` operations and reads its stored weight bytes once; attention
over a context of ``c`` keys needs ``4 c dh`` operations per query head
(scores and values) and reads the ``c`` packed K and V rows once.
"""
from __future__ import annotations

G = 16


def projection_shapes(arch: dict) -> list[tuple[int, int]]:
    """(K, N) of every projection GEMM of one layer."""
    d, dh = arch["d_model"], arch["head_dim"]
    q, kv = arch["n_heads"] * dh, arch["n_kv_heads"] * dh
    out = [(d, q), (d, kv), (d, kv), (q, d), (d, arch["d_ff"]),
           (arch["d_ff"], d)]
    if arch["mlp_type"] in ("swiglu", "geglu"):
        out.append((d, arch["d_ff"]))
    return out


def projection_params(arch: dict) -> int:
    return arch["n_layers"] * sum(k * n for k, n in projection_shapes(arch))


def w4a16_call(m: int, k: int, n: int) -> tuple[float, float]:
    """One W4A16 GEMM of ``m`` bf16 rows: 4-bit payload and one E4M3 byte
    per 16x16 tile read once, activations in (bf16) and out (f32)."""
    ops = 2.0 * m * k * n
    nbytes = k * n / 2 + (k // G) * (n // G) + 2.0 * m * k + 4.0 * m * n
    return ops, nbytes


def w4a16_step(arch: dict, m: int) -> tuple[float, float]:
    """Every W4A16 call of one step whose GEMMs have ``m`` rows."""
    ops = nbytes = 0.0
    for k, n in projection_shapes(arch):
        o, b = w4a16_call(m, k, n)
        ops += o
        nbytes += b
    return ops * arch["n_layers"], nbytes * arch["n_layers"]


def kv_row_bytes(arch: dict) -> float:
    """Packed bytes of one position's K and V rows in one layer."""
    dh = arch["head_dim"]
    return 2.0 * arch["n_kv_heads"] * (dh / 2 + dh / G)


def attn_decode_step(arch: dict, contexts: list[int]) -> tuple[float, float]:
    """The paged decode-attention kernel over every layer of one step:
    one query row per entry of ``contexts`` (keys attended, after the
    window), each reading its packed K/V rows once."""
    h, dh = arch["n_heads"], arch["head_dim"]
    keys = float(sum(contexts))
    ops = 4.0 * keys * h * dh
    nbytes = keys * kv_row_bytes(arch) + 8.0 * len(contexts) * h * dh
    return ops * arch["n_layers"], nbytes * arch["n_layers"]


def attended(arch: dict, context: int) -> int:
    w = arch.get("window") or 0
    return min(context, w) if w else context


def model_flops(arch: dict, *, tokens: int, contexts: list[int],
                emitted: int) -> float:
    """Model operations of ``tokens`` processed tokens whose attention
    contexts are ``contexts`` (one entry per token), plus the LM head of
    ``emitted`` tokens."""
    attn = 4.0 * arch["n_heads"] * arch["head_dim"] * arch["n_layers"] * sum(
        attended(arch, c) for c in contexts)
    head = 2.0 * arch["d_model"] * arch["vocab"] * emitted
    return 2.0 * projection_params(arch) * tokens + attn + head

"""Plain reference of the dense transformer the benchmark serves.

It imports nothing of the program.  It reads the configuration file's
``arch`` section and the arrays that ``bench/weights.py`` draws from the
seed, decodes the MixFP4 bytes with its own decoder, and runs the whole
forward pass of the published architecture with the repository's stated
departures (RMSNorm without biases, embedding tied to the LM head):

    x = E[tokens]
    per layer:  h = rms(x) * g1;  q, k, v = h Wq, h Wk, h Wv;  RoPE on q, k
                k, v <- MixFP4(k), MixFP4(v)   (the packed KV cache: 1-D
                        blocks of 16 along the head, per-tensor scale 1)
                x += softmax(q k^T / sqrt(dh) + causal/window mask) v Wo
                h = rms(x) * g2;  x += (silu(h Wg) * h Wu) Wd   (SwiGLU)
                                  or  gelu_tanh(h Wu) Wd        (GeLU)
    logits = rms(x) * gf E^T

in the precision the configuration states: W4A16 serving of checkpoints
published in bfloat16, so activations are held in bfloat16 (the residual
stream, every GEMM input and output, norms, RoPE, attention output) and
all arithmetic between them is float32, every matmul at
``Precision.HIGHEST`` (exact products of the bf16 inputs and the decoded
weights, accumulated in f32).  Several requests are packed into one
sequence of a fixed length with segment ids, so one compiled program
serves every sample.  ``act="fp8"`` is the control: every GEMM input
(projections and LM head) is rounded to float8 E4M3 under a per-row scale,
the next precision below bfloat16.  Every rounding is integer arithmetic
on the f32 bits and every power of two is built from exponent bits, so
the results do not depend on how a compiler treats converts or ``exp2``.

``own_pass`` runs the stack on its own K/V rows; ``forced_pass`` runs it
teacher-forced, each layer attending over K/V rows it is given (those the
side under test wrote), and compares its own rows and logits with them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

G = 16
_E2M1 = (0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0)
_E4M3_MAX = 448.0
HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# MixFP4 decode and the KV row quantizer, written from the format itself
# ---------------------------------------------------------------------------
def e4m3(byte):
    """E4M3 magnitude of bits 6..0 of a scale byte (bit 7 is the type).
    The power of two is built from its exponent bits: ``exp2`` is not
    exact on the TPU."""
    b = byte.astype(jnp.int32) & 0x7F
    e, m = b >> 3, (b & 7).astype(jnp.float32)
    two = jax.lax.bitcast_convert_type((e + 120) << 23, jnp.float32)
    return jnp.where(e > 0, two * (1 + m / 8), m * 2.0 ** -9)


def code_value(nib, e1m2):
    """Signed value of a 4-bit code [s|m2 m1 m0]: E2M1, or E1M2 read on
    its doubled lattice {0..7}."""
    mag = nib & 7
    e2m1 = jnp.asarray(_E2M1, jnp.float32)[mag]
    val = jnp.where(e1m2, mag.astype(jnp.float32), e2m1)
    return jnp.where((nib >> 3) & 1 == 1, -val, val)


def dequant_2d(payload, scales, scale32):
    """(K/2, N) payload, (K/16, N/16) scales -> (K, N) f32.  The low nibble
    of byte (i, n) is row 2i, the high one row 2i+1."""
    k2, n = payload.shape
    nib = jnp.stack([payload & 0xF, payload >> 4], axis=1).reshape(2 * k2, n)
    t = jnp.repeat(jnp.repeat((scales >> 7) == 1, G, 0), G, 1)
    s = jnp.repeat(jnp.repeat(e4m3(scales), G, 0), G, 1)
    return code_value(nib.astype(jnp.int32), t) * s * scale32


def _bits(x):
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def _float(b):
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _round_e4m3(x):
    """Round to the nearest E4M3 value, ties to even, saturating at 448.
    Integer arithmetic on the f32 bits, not a convert: inside a fusion the
    TPU compiler may keep a value in f32 across a lower-precision round
    trip."""
    a = jnp.minimum(jnp.abs(x), _E4M3_MAX)
    b = _bits(a)
    normal = _float((b + 0x7FFFF + ((b >> 20) & 1)) & ~jnp.uint32(0xFFFFF))
    sub = jnp.round(a * 512.0) * (1.0 / 512.0)       # spacing 2^-9 below 2^-6
    return jnp.sign(x) * jnp.where(a < 2.0 ** -6, sub, normal)


def _nearest(y, levels):
    """|y| to the nearest level, ties to the even index, sign kept."""
    lv = jnp.asarray(levels, jnp.float32)
    mids = (lv[1:] + lv[:-1]) / 2
    a = jnp.abs(y)
    idx = jnp.sum(a[..., None] > mids, axis=-1)
    tie = (idx < 7) & (a == mids[jnp.minimum(idx, 6)]) & (idx % 2 == 1)
    idx = jnp.minimum(idx + tie, 7)
    return jnp.sign(y) * lv[idx]


def mixfp4_rows(x):
    """Quantize and decode rows in 1-D blocks of 16 along the last axis at
    per-tensor scale 1: per block, E2M1 at scale E4M3(max/6) and E1M2 at
    E4M3(max/7), the lower squared error wins (E2M1 on a tie)."""
    shape = x.shape
    xb = x.reshape(*shape[:-1], shape[-1] // G, G).astype(jnp.float32)
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    best, best_err = None, None
    for levels, target in ((_E2M1, 6.0), (tuple(range(8)), 7.0)):
        s = _round_e4m3(jnp.clip(amax * (1.0 / target), 0.0, _E4M3_MAX))
        s = jnp.where((amax > 0) & (s <= 0), 2.0 ** -9, s)
        s = jnp.where(amax > 0, s, 1.0)
        deq = _nearest(xb * (1.0 / s), levels) * s
        err = jnp.mean(jnp.square(deq - xb), axis=-1, keepdims=True)
        if best is None:
            best, best_err = deq, err
        else:
            best = jnp.where(err < best_err, deq, best)
    return best.reshape(shape)


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------
def _fp8_rows(a):
    """Round each row to float8 E4M3 under its own scale max|row| / 448."""
    amax = jnp.max(jnp.abs(a), axis=-1, keepdims=True)
    s = jnp.where(amax > 0, amax / _E4M3_MAX, 1.0)
    return _round_e4m3(a / s) * s


def _bf(x):
    """Round to bfloat16, the activations' storage precision: nearest,
    ties to even, on the f32 bits (see ``_round_e4m3``)."""
    b = _bits(x)
    return _float((b + 0x7FFF + ((b >> 16) & 1)) & ~jnp.uint32(0xFFFF))


def _mm(a, w, act):
    """A GEMM with its input in the activation precision ``act``; f32
    accumulation, output rounded to bf16."""
    a = _fp8_rows(a) if act == "fp8" else _bf(a)
    return _bf(jnp.dot(a, w, precision=HIGHEST))


def _rms(x, g, eps):
    return _bf(x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
               * g)


def _rope(x, pos, theta):
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = jnp.cos(ang), jnp.sin(ang)
    return _bf(jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1))


def _attention(q, k, v, pos, seg, window, block=1024):
    """q (T, H, dh), k/v (T, Hkv, dh) -> (T, H, dh), one KV head and one
    block of queries at a time."""
    t, h, dh = q.shape
    hkv = k.shape[1]
    g = h // hkv
    nb = t // block
    qg = q.reshape(nb, block, hkv, g, dh).transpose(2, 0, 3, 1, 4)
    qpos, qseg = pos.reshape(nb, block), seg.reshape(nb, block)

    def one(args):
        qh, kh, vh, p, sg = args             # (G,blk,dh) (T,dh) (T,dh) (blk,)
        ok = (sg[:, None] == seg[None, :]) & (pos[None, :] <= p[:, None])
        if window:
            ok &= pos[None, :] > p[:, None] - window
        s = jnp.einsum("gqd,kd->gqk", qh, kh, precision=HIGHEST)
        s = jnp.where(ok, s * dh ** -0.5, -jnp.inf)
        return jnp.einsum("gqk,kd->gqd", jax.nn.softmax(s, axis=-1), vh,
                          precision=HIGHEST)

    def head(args):
        qh, kh, vh = args                    # (nb,G,blk,dh) (T,dh) (T,dh)
        return jax.lax.map(lambda x: one((x[0], kh, vh, x[1], x[2])),
                           (qh, qpos, qseg))

    o = jax.lax.map(head, (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    # (Hkv, nb, G, blk, dh) -> (T, H, dh)
    return _bf(o.transpose(1, 3, 0, 2, 4).reshape(t, h, dh))


def _stack(w, a, act, tokens, pos, seg, teacher, per_layer):
    """Every layer.  Layer ``l`` attends over ``teacher(l)`` = (K, V) rows
    when given, else over its own; ``per_layer(l, k, v)`` says what each
    layer emits from its own MixFP4 K and V rows.  Returns the final
    normed residual stream and the stacked emissions."""
    hq, hkv, dh = a["n_heads"], a["n_kv_heads"], a["head_dim"]
    eps, t = a["norm_eps"], tokens.shape[0]
    x = _bf(w["embed"][tokens])

    def layer(x, xs):
        lw, tk, tv = xs
        deq = {n: dequant_2d(*lw["proj"][n]) for n in lw["proj"]}
        h = _rms(x, lw["ln_attn"], eps)
        q = _rope(_mm(h, deq["wq"], act).reshape(t, hq, dh), pos,
                  a["rope_theta"])
        k = mixfp4_rows(_rope(_mm(h, deq["wk"], act).reshape(t, hkv, dh),
                              pos, a["rope_theta"]))
        v = mixfp4_rows(_mm(h, deq["wv"], act).reshape(t, hkv, dh))
        ka, va = (k, v) if tk is None else (tk.astype(jnp.float32),
                                            tv.astype(jnp.float32))
        o = _attention(q, ka, va, pos, seg, a["window"])
        x = _bf(x + _mm(o.reshape(t, hq * dh), deq["wo"], act))
        h = _rms(x, lw["ln_mlp"], eps)
        up = _mm(h, deq["w_up"], act)
        if a["mlp_type"] == "swiglu":
            mid = _bf(jax.nn.silu(_mm(h, deq["w_gate"], act)) * up)
        else:
            mid = _bf(jax.nn.gelu(up, approximate=True))
        return _bf(x + _mm(mid, deq["w_down"], act)), per_layer(k, v, ka, va)

    lws = {"proj": w["proj"], "ln_attn": w["ln_attn"], "ln_mlp": w["ln_mlp"]}
    tk, tv = teacher if teacher is not None else (None, None)
    x, out = jax.lax.scan(layer, x, (lws, tk, tv))
    return _rms(x, w["ln_f"], eps), out


def _logits_rows(x, embed, vocab, act, fn, block=1024):
    """``fn(logits, rows)`` over blocks of ``block`` rows of the LM head's
    output, concatenated."""
    if act == "fp8":
        x = _fp8_rows(x)
    e = embed[:vocab]
    t = x.shape[0]
    rows = jnp.arange(t).reshape(t // block, block)
    out = jax.lax.map(
        lambda r: fn(jnp.dot(x[r], e.T, precision=HIGHEST), r), rows)
    return out.reshape(t, *out.shape[2:])


@functools.partial(jax.jit, static_argnames=("arch", "act"))
def _own(w, tokens, pos, seg, *, arch, act):
    a = dict(arch)
    x, (k, v) = _stack(w, a, act, tokens, pos, seg, None,
                       lambda k, v, ka, va: (k.astype(jnp.bfloat16),
                                             v.astype(jnp.bfloat16)))
    top = _logits_rows(x, w["embed"], a["vocab"], act,
                       lambda lg, r: jnp.argmax(lg, -1))
    return k, v, top


@functools.partial(jax.jit, static_argnames=("arch",))
def _forced(w, tokens, pos, seg, tk, tv, valid, picked, *, arch):
    a = dict(arch)
    m = valid[:, None, None]

    def stats(k, v, ka, va):
        return jnp.stack([jnp.sum(jnp.where(m, jnp.square(ka - k), 0.0)),
                          jnp.sum(jnp.where(m, jnp.square(k), 0.0)),
                          jnp.sum(jnp.where(m, jnp.square(va - v), 0.0)),
                          jnp.sum(jnp.where(m, jnp.square(v), 0.0)),
                          jnp.sum(m & (ka != k)) / jnp.sum(m & (k == k)),
                          jnp.sum(m & (va != v)) / jnp.sum(m & (v == v))])

    x, st = _stack(w, a, "bf16", tokens, pos, seg, (tk, tv), stats)
    gap = _logits_rows(x, w["embed"], a["vocab"], "bf16",
                       lambda lg, r: jnp.max(lg, -1) - lg[
                           jnp.arange(lg.shape[0]), picked[r]])
    return st, gap


ARCH_KEYS = ("n_layers", "n_heads", "n_kv_heads", "head_dim", "norm_eps",
             "rope_theta", "window", "mlp_type", "vocab")


def own_pass(weights: dict, arch: dict, tokens, pos, seg, act: str):
    """A whole forward pass of a packed sequence that attends over its own
    K/V rows: ``tokens``, ``pos`` (position within its request) and
    ``seg`` (request id) are (T,) int32, and positions attend only within
    their own segment.  Returns each layer's MixFP4 K and V rows
    (L, T, Hkv, dh) bf16 and the first token by its logits at each row."""
    return _own(weights, jnp.asarray(tokens), jnp.asarray(pos),
                jnp.asarray(seg), arch=_freeze(arch), act=act)


def forced_pass(weights: dict, arch: dict, tokens, pos, seg, kv, valid,
                picked):
    """The bf16 reference teacher-forced on given K/V rows: layer ``l``
    attends over ``kv[0][l]``, ``kv[1][l]`` (L, T, Hkv, dh) instead of its
    own rows, so each layer starts from the same history as the side under
    test.  Returns per layer, over the rows where ``valid``, the sums
    (|K_given - K_own|^2, |K_own|^2, the same for V) and the shares of K
    and V elements where the given rows differ from the reference's own,
    and per row the gap of token ``picked`` below the best logit."""
    return _forced(weights, jnp.asarray(tokens), jnp.asarray(pos),
                   jnp.asarray(seg), kv[0], kv[1], jnp.asarray(valid),
                   jnp.asarray(picked), arch=_freeze(arch))


def _freeze(arch: dict) -> tuple:
    return tuple(sorted((k, arch[k]) for k in ARCH_KEYS))


@jax.jit
def decode_kv(payload, scales, scale32):
    """Packed 1-D rows (L, ..., dh/2) payload and (L, ..., dh/16) scales,
    (L,) per-tensor scales -> values (L, ..., dh) bf16 (exact: a code times
    an E4M3 scale has at most 7 significant bits).  Byte i holds element
    2i (low nibble) and 2i+1 (high)."""
    nib = jnp.stack([payload & 0xF, payload >> 4], axis=-1)
    nib = nib.reshape(*payload.shape[:-1], 2 * payload.shape[-1])
    t = jnp.repeat((scales >> 7) == 1, G, axis=-1)
    s = jnp.repeat(e4m3(scales), G, axis=-1)
    s32 = scale32.reshape(-1, *([1] * (payload.ndim - 1)))
    return (code_value(nib.astype(jnp.int32), t) * s * s32).astype(
        jnp.bfloat16)


def pack_sequences(seqs: list, length: int):
    """Concatenate token lists into (tokens, pos, seg) of ``length``; the
    tail is padding in a segment of its own."""
    if length % 1024:
        raise ValueError(f"packed length {length} is not a multiple of 1024")
    tokens = np.zeros(length, np.int32)
    pos = np.zeros(length, np.int32)
    seg = np.full(length, len(seqs), np.int32)
    at = 0
    for i, s in enumerate(seqs):
        n = len(s)
        tokens[at:at + n] = s
        pos[at:at + n] = np.arange(n)
        seg[at:at + n] = i
        at += n
    if at > length:
        raise ValueError(f"{at} tokens do not fit a packed length {length}")
    return tokens, pos, seg

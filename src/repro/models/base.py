"""Model-zoo foundation: configs, parameter/spec pytrees, shared layers.

Parameters are plain nested dicts whose leaves are ``Param(value, spec)``
pairs built at init; ``unzip_params`` splits them into a value tree (what the
optimizer/train step carry) and a PartitionSpec tree (what pjit shards).  The
single source of truth for sharding is therefore the init code itself.

Sharding convention on the production mesh (see launch/mesh.py):
  "data"  — batch / tokens (+ "pod" prepended for multi-pod via spec rewrite)
  "model" — TP: attention heads, FFN hidden, vocab; EP: experts

GSPMD pads non-divisible dims (e.g. phi3's 40 heads on a 16-way model axis);
we accept activation padding but never let it touch the large persistent
buffers (KV caches shard over sequence instead — see serving/).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass, field
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import hadamard, qtensor
from repro.core.qgemm import QuantConfig, qgemm

__all__ = [
    "ArchConfig",
    "Param",
    "unzip_params",
    "param_count",
    "PROJECTION_KEYS",
    "is_packable_projection",
    "pack_projections",
    "decode_positions",
    "rms_norm",
    "apply_rope",
    "qlinear",
    "linear_init",
    "embed_init",
    "attention",
    "mlp",
    "mlp_init",
    "attn_init",
    "build_model",
    "shard",
    "KV_SCALE32",
    "quantize_kv_rows",
    "slot_take",
    "slot_put",
]


# ---------------------------------------------------------------------------
# Config
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ArchConfig:
    name: str = "model"
    family: str = "dense"        # dense|moe|ssm|hybrid|encdec|vlm
    n_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 1024
    vocab: int = 512
    head_dim: int = 0            # 0 -> d_model // n_heads
    mlp_type: str = "swiglu"     # swiglu|gelu|geglu
    rope_theta: float = 10_000.0
    qk_norm: bool = False
    softcap_attn: float = 0.0
    softcap_final: float = 0.0
    window: int = 0              # sliding-window attention (0 = full)
    local_global_period: int = 0 # gemma2: local except every p-th layer global
    attn_chunk: int = 1024       # query-chunked attention block
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    d_ff_expert: int = 0
    shared_expert_ff: int = 0
    ep_mode: str = "expert"      # 'expert' (EP over model) | 'ffn' (TP over d_ff)
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    # --- SSM ---
    ssm_state: int = 0
    ssm_conv: int = 4
    ssm_expand: int = 2
    ssm_version: int = 1         # 1 = Mamba-1, 2 = Mamba-2 (SSD)
    ssm_head_dim: int = 64       # Mamba-2 P
    ssm_chunk: int = 128
    attn_period: int = 0         # hybrid (zamba2): shared attn every k layers
    # --- encoder-decoder ---
    n_dec_layers: int = 0        # >0 => enc-dec; n_layers = encoder depth
    # --- modality stubs ---
    n_prefix_embeds: int = 0     # VLM patches / audio frames prepended
    frontend: str = ""           # 'vision'|'audio'|''
    # --- numerics ---
    quant: QuantConfig = field(default_factory=lambda: QuantConfig(method="mixfp4"))
    norm_eps: float = 1e-5
    emb_scale: bool = False      # gemma-style sqrt(d) embedding scaling

    @property
    def dh(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Param/spec machinery
# ---------------------------------------------------------------------------
class Param(NamedTuple):
    value: jax.Array
    spec: Any  # PartitionSpec


def _is_param(x) -> bool:
    return isinstance(x, Param)


def unzip_params(tree):
    """Param tree -> (value tree, spec tree)."""
    values = jax.tree.map(lambda p: p.value, tree, is_leaf=_is_param)
    specs = jax.tree.map(lambda p: p.spec, tree, is_leaf=_is_param)
    return values, specs


def param_count(values) -> int:
    return sum(int(np.prod(v.shape)) for v in jax.tree.leaves(values))


def _active_mesh():
    """The mesh from the enclosing `with mesh:` context, or None."""
    from jax._src import mesh as mesh_lib
    m = mesh_lib.thread_resources.env.physical_mesh
    return m if (m.axis_names and not m.empty) else None


def _whole_on_each_device(fn, *args):
    """Call a Pallas kernel under the ambient mesh, if any.  Mosaic kernels
    cannot be partitioned automatically, so on a multi-device mesh every
    device runs the call whole on replicated operands."""
    m = _active_mesh()
    if m is None or m.size == 1:
        return fn(*args)
    from repro.distributed.sharding import shard_map
    return shard_map(fn, mesh=m, in_specs=P(), out_specs=P())(*args)


# Global sharding regime.
#  'fsdp' (train shapes): the logical 'data' axis spans data x model (x pod)
#   — batch shards over every chip, weights stay model-sharded in HBM and
#   are gathered per layer (ZeRO-3 pattern); 'model' constraints on
#   activations are dropped (the axis is busy with batch).
#  'sp' (prefill shapes): batch over data, SEQUENCE over model — projections
#   are token-local (no row-parallel psums of (B, 32k, D) activations);
#   attention gathers the (small, GQA) K/V per layer; weights model-sharded
#   with FSDP-style gathers.
#  default 'tp' (decode): 'data' = data (x pod), 'model' = TP.
_STATE = {"fsdp": False, "sp": False}


def set_fsdp(on: bool):
    _STATE["fsdp"] = bool(on)


def set_sp(on: bool):
    _STATE["sp"] = bool(on)


def batch_axes(mesh=None) -> tuple:
    m = mesh or _active_mesh()
    names = m.axis_names if m is not None else ("data",)
    ax = (("pod",) if "pod" in names else ()) + ("data",)
    if _STATE["fsdp"] and "model" in names:
        ax = ax + ("model",)
    return ax


def shard(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint against the ambient mesh; no-op without one.

    The logical 'data' axis resolves per the active regime (see _STATE);
    'model' activation constraints are dropped under FSDP."""
    m = _active_mesh()
    if m is None:
        return x
    names = m.axis_names
    bax = batch_axes(m)
    if _STATE["sp"]:
        # sequence-parallel serving: (B, S, ...) -> batch over data,
        # sequence over model; drop all other activation constraints
        if len(spec) >= 2 and spec[0] == "data":
            parts = [bax if len(bax) > 1 else "data", "model"] + \
                [None] * (len(spec) - 2)
            return jax.lax.with_sharding_constraint(x, P(*parts))
        return x
    parts = []
    for p in spec:
        if p == "data":
            parts.append(bax if len(bax) > 1 else "data")
        elif p == "model" and _STATE["fsdp"]:
            parts.append(None)
        elif p is None or isinstance(p, tuple) or p in names:
            parts.append(p)
        else:
            return x  # unknown axis for this mesh: skip the constraint
    return jax.lax.with_sharding_constraint(x, P(*parts))


def linear_init(key, d_in: int, d_out: int, spec=P(None, "model"),
                scale: float | None = None) -> Param:
    s = scale if scale is not None else 1.0 / math.sqrt(d_in)
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * s
    return Param(w, spec)


def padded_vocab(vocab: int, multiple: int = 256) -> int:
    """Vocab rows padded for clean TP sharding (standard practice; the
    logical vocab is unchanged — lm_logits slices back)."""
    return ((vocab + multiple - 1) // multiple) * multiple


def embed_init(key, vocab: int, d: int) -> Param:
    w = jax.random.normal(key, (padded_vocab(vocab), d), jnp.float32) * 0.02
    return Param(w, P("model", None))


def norm_init(d: int) -> Param:
    return Param(jnp.ones((d,), jnp.float32), P(None))


# ---------------------------------------------------------------------------
# Elementwise / norm / rope
# ---------------------------------------------------------------------------
def rms_norm(x: jax.Array, g: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return ((x32 * jax.lax.rsqrt(var + eps)) * g).astype(x.dtype)


def _rope_freqs(dh: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, dh); positions: (B, S) or (S,)."""
    dh = x.shape[-1]
    freqs = _rope_freqs(dh, theta)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,dh/2)
    cos = jnp.cos(ang)[..., None, :]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Quantized linear (the paper's GEMM boundary)
# ---------------------------------------------------------------------------
def qlinear(x: jax.Array, w, ctx: "Ctx", tag: int) -> jax.Array:
    """All projection GEMMs route through the quantized boundary.

    Dense ``w`` (training): the Fig. 7 qdq-simulated ``qgemm`` with SR/RHT
    on the backward pass.  Packed ``QTensor`` ``w`` (serving): ``qmm``
    serves straight from the 4.5-bit wire format through the W4A16 kernel —
    no dense copy of the weight ever exists.  A packed weight that carries
    a logical ``pspec`` (``QTensor.with_sharding``) under an active mesh
    dispatches to ``qmm_sharded``: the kernel runs per model-axis shard
    under ``shard_map``, keeping the operands packed AND sharded
    (docs/sharding.md).

    With ``ctx.act_quant == "mixfp4"`` (W4A4 serving, docs/serving.md) the
    dense activation is quantized on the fly in the W4A4 kernel's fused
    prologue (``qmm(x, w, fuse_act_quant=True)`` — ONE Pallas dispatch per
    projection; under a mesh, ``qmm_sharded`` with the fused flag) using
    the same type-in-sign E4M3 block-scale wire encoding as every other
    wire tensor, under the PER-ROW level-2 scale contract: each token
    row's bytes — and therefore its output row — are a pure function of
    that row, independent of batchmates and padding.
    ``"mixfp4-2pass-rowscale"`` is the explicit two-dispatch composition
    the fused path is bitwise-identical to — ``quantize_rows(per_row=True)``
    onto the weight's packed ``Kp`` grid, then the per-row W4A4 kernel —
    kept as the serving-level oracle and for A/B benchmarks.
    ``ctx.act_rht`` layers the grouped random Hadamard transform ahead of
    the quantizer on both spellings (fused in the same VMEM pass;
    ``ops.rht_rows`` for the composition) — the packed weight must carry
    the matching transform (``pack_projections(act_rht=True)``).
    ``"mixfp4-2pass"`` is the legacy PER-TENSOR two-dispatch spelling
    (Alg. 1 line 4 verbatim, batch-coupled), kept as the A/B baseline;
    ``"mixfp4-qdq"`` is its debugging oracle: the SAME per-tensor wire
    bytes are decoded back to dense rows and served W4A16 — what the
    W4A4 kernel computes, minus its fused in-VMEM decode.
    """
    if isinstance(w, qtensor.QTensor):
        m = _active_mesh()
        kernel_w = (isinstance(w.layout, qtensor.BlockLayout2D)
                    and w.payload.ndim == 2)
        sharded = (m is not None and w.pspec is not None and kernel_w
                   and qtensor.kn_partitions(w) != (None, None))
        aq = ctx.act_quant
        if (aq == "mixfp4" and kernel_w
                and not isinstance(x, qtensor.QTensor)):
            lead, k = x.shape[:-1], x.shape[-1]
            x2 = x.reshape(-1, k)
            signs = (hadamard.serve_signs(2 * w.payload.shape[0])
                     if ctx.act_rht else None)
            y = (qtensor.qmm_sharded(x2, w, mesh=m, fuse_act_quant=True,
                                     per_row_act=True, act_rht_signs=signs)
                 if sharded else
                 qtensor.qmm(x2, w, fuse_act_quant=True, per_row_act=True,
                             act_rht_signs=signs))
            return y.reshape(*lead, y.shape[-1]).astype(x.dtype)
        if (aq in ("mixfp4-2pass", "mixfp4-2pass-rowscale", "mixfp4-qdq")
                and kernel_w and not isinstance(x, qtensor.QTensor)):
            from repro.kernels import ops  # deferred: kernels import core
            kp = 2 * w.payload.shape[0]
            lead, k = x.shape[:-1], x.shape[-1]
            per_row = aq == "mixfp4-2pass-rowscale"
            x2 = x.reshape(-1, k)
            if per_row and ctx.act_rht:
                # transform on the packed Kp grid BEFORE quantizing — the
                # same grid/signs the fused prologue and the pack-time
                # weight transform use, so H/D cancel in the dot product
                x2f = x2.astype(jnp.float32)
                if kp != k:
                    x2f = jnp.pad(x2f, ((0, 0), (0, kp - k)))
                x2 = _whole_on_each_device(
                    lambda a: ops.rht_rows(a, hadamard.serve_signs(kp)), x2f)
            qx = _whole_on_each_device(
                lambda a: qtensor.quantize_rows(a, pad_to=kp,
                                                per_row=per_row), x2)
            if aq != "mixfp4-qdq":
                y = (qtensor.qmm_sharded(qx, w, mesh=m) if sharded
                     else qtensor.qmm(qx, w))
            else:
                # Oracle: decode the SAME wire bytes in the kernel's
                # factored-scale form (Eq. 35) — value x block-scale rows
                # (exact in bf16: <= 7 significand bits), per-tensor scale
                # applied to the f32 output — and serve them W4A16.
                xd = qtensor.QTensor(
                    qx.payload, qx.scales, jnp.ones((), jnp.float32),
                    qx.method, qx.layout, qx.shape, "float32").dequantize()
                y = (qtensor.qmm_sharded(xd, w, mesh=m) if sharded
                     else qtensor.qmm(xd, w)) * qx.scale32
            return y.reshape(*lead, y.shape[-1]).astype(x.dtype)
        if sharded and not isinstance(x, qtensor.QTensor):
            return qtensor.qmm_sharded(x, w, mesh=m).astype(x.dtype)
        return qtensor.qmm(x, w).astype(x.dtype)
    return qgemm(ctx.quant, x, w, jax.random.fold_in(ctx.key, tag))


# Projection-weight leaves consumed through qlinear — exactly the GEMMs the
# paper quantizes (embeddings, norms and the LM head stay high-precision per
# the paper's exclusions).  attn/mlp names from attn_init/mlp_init below;
# in/x/dt/out_proj from the Mamba blocks (models/mamba.py).
PROJECTION_KEYS = frozenset(
    {"wq", "wk", "wv", "wo", "w_up", "w_down", "w_gate",
     "in_proj", "x_proj", "dt_proj", "out_proj"})


def is_packable_projection(key: str, leaf) -> bool:
    """One predicate for "does ServeEngine pack this leaf" — shared with the
    dryrun HBM accounting so report and engine can't drift.  Matches any
    projection-named leaf whose trailing (K, N) matrix fills at least one
    16x16 tile; leading dims (scan layer stacking, MoE expert dims) ride
    along as QTensor batch dimensions."""
    return (key in PROJECTION_KEYS and getattr(leaf, "ndim", 0) >= 2
            and min(leaf.shape[-2:]) >= 16)


@functools.partial(jax.jit, static_argnums=2)
def _quantize_stack(flat, signs, spec):
    """Quantize a (L, K, N) stack one (K, N) matrix at a time: the device
    holds one matrix's quantizer temporaries, not the whole stack's (a
    gemma2-2b MLP stack is 2.2 GB in f32).  ``signs`` applies the serve-time
    RHT along K first."""
    def one(w):
        if signs is not None:
            w = hadamard.rht(w, signs, axis=-2, group=16)
        return qtensor.quantize(w, spec)
    return jax.lax.map(one, flat)


def pack_projections(params, method: str = "mixfp4",
                     block: tuple[int, int] = (16, 16),
                     act_rht: bool = False):
    """Replace every projection-weight leaf of a parameter value tree with a
    packed 2-D-tiled :class:`~repro.core.qtensor.QTensor`.

    Leaves with leading batch dims — ``(n_layers, K, N)`` from the
    ``lax.scan`` layout, ``(n_layers, E, K, N)`` for scan-stacked MoE
    experts — are quantized one trailing matrix at a time (``lax.map`` in
    one jit); the result is one QTensor whose children carry the leading
    dims, which scan/``lax.map`` slice transparently.  Projection leaves
    that are already packed QTensors (another engine's tree) pass through
    (not with ``act_rht``, which must transform the dense weights).
    Returns ``(packed_tree, packed_bytes, dense_bytes)`` where the byte
    counts cover the projection leaves (dense at bf16 rates).

    ``act_rht=True`` applies the serve-time grouped random Hadamard
    transform along each projection's K axis BEFORE quantizing (signs from
    ``hadamard.serve_signs`` — the deterministic diagonal ``qlinear``'s
    fused prologue applies to activations, so ``(HDx)·(HDW) = x·W`` up to
    quantization), and records the diagonals in a top-level
    ``"rht_signs"`` entry of the returned tree ``{str(K): (K,) f32}`` so
    checkpoints carry the exact ``D`` alongside the transformed bytes.
    Requires every projection K to be a multiple of the transform group
    (16) — the transform must live on the same padded grid as the packed
    payload.
    """
    spec = qtensor.QuantSpec(method, qtensor.BlockLayout2D(*block))
    stats = {"packed": 0, "dense": 0}
    signs_used: dict[str, jax.Array] = {}

    def convert(w):
        signs = None
        if act_rht:
            k_ax = w.shape[-2]
            if k_ax % 16:
                raise ValueError(
                    f"pack_projections(act_rht=True): projection K={k_ax} "
                    f"must be a multiple of the RHT group (16)")
            signs = hadamard.serve_signs(k_ax)
            signs_used[str(k_ax)] = signs
        lead = w.shape[:-2]
        if lead:
            flat = w.reshape((-1,) + w.shape[-2:])
            qt = _quantize_stack(flat, signs, spec)
            if len(lead) > 1:
                qt = qtensor.QTensor(
                    qt.payload.reshape(lead + qt.payload.shape[1:]),
                    qt.scales.reshape(lead + qt.scales.shape[1:]),
                    qt.scale32.reshape(lead + qt.scale32.shape[1:]),
                    qt.method, qt.layout, qt.shape, qt.dtype)
        else:
            if signs is not None:
                w = hadamard.rht(w, signs, axis=-2, group=16)
            qt = qtensor.quantize(w, spec)
        stats["packed"] += qt.nbytes
        stats["dense"] += w.size * 2
        return qt

    def count(qt):
        if act_rht:
            raise ValueError(
                "pack_projections(act_rht=True) transforms the dense "
                "weights before packing; this projection is already packed")
        stats["packed"] += qt.nbytes
        stats["dense"] += 2 * math.prod(qt.shape) * math.prod(
            qt.payload.shape[:-2])
        return qt

    def walk(node):
        if isinstance(node, dict):
            return {k: (convert(v) if is_packable_projection(k, v)
                        else count(v) if (k in PROJECTION_KEYS and
                                          isinstance(v, qtensor.QTensor))
                        else walk(v))
                    for k, v in node.items()}
        return node

    packed = walk(params)
    if act_rht and isinstance(packed, dict):
        packed = dict(packed)
        packed["rht_signs"] = signs_used
    return packed, stats["packed"], stats["dense"]


def decode_positions(cache_len, b: int) -> jax.Array:
    """(B, 1) absolute positions for a single-token decode step from a
    scalar or per-sequence ``(B,)`` cache length."""
    cl = jnp.asarray(cache_len)
    if cl.ndim:
        cl = cl[:, None]
    return cl + jnp.zeros((b, 1), jnp.int32)


@dataclass(frozen=True)
class Ctx:
    """Per-call context: PRNG key for SR/RHT, quant config, the active
    mesh (None = single-device; MoE then skips its collectives), and the
    serving activation format: ``act_quant="mixfp4"`` makes every
    packed-weight ``qlinear`` run the fused quantize+GEMM W4A4 kernel in
    one dispatch under PER-ROW activation scales
    (``"mixfp4-2pass-rowscale"`` = the explicit
    quantize_rows(per_row=True) -> W4A4 two-dispatch composition it is
    bitwise-identical to; ``"mixfp4-2pass"`` = the legacy per-tensor
    two-dispatch baseline; ``"mixfp4-qdq"`` = its dequantize-then-W4A16
    oracle; anything else = dense bf16 activations, W4A16).
    ``act_rht=True`` (with the per-row spellings) applies the grouped
    random Hadamard transform to activations ahead of the quantizer —
    fused into the same GEMM prologue — against RHT-transformed packed
    weights (``pack_projections(act_rht=True)``)."""
    key: jax.Array
    quant: QuantConfig
    mesh: Any = None
    data_axes: tuple = ("data",)      # ("pod","data") on the multi-pod mesh
    model_axis: str = "model"
    act_quant: str = "bf16"
    act_rht: bool = False

    def fold(self, i: int) -> "Ctx":
        return dataclasses.replace(self, key=jax.random.fold_in(self.key, i))

    def with_key(self, key: jax.Array) -> "Ctx":
        return dataclasses.replace(self, key=key)

    @property
    def model_size(self) -> int:
        return 1 if self.mesh is None else self.mesh.shape[self.model_axis]

    @property
    def data_size(self) -> int:
        if self.mesh is None:
            return 1
        n = 1
        for a in self.data_axes:
            n *= self.mesh.shape[a]
        return n


# ---------------------------------------------------------------------------
# Attention (GQA + RoPE + SWA + softcap + qk-norm), query-chunked
# ---------------------------------------------------------------------------
def attn_init(key, cfg: ArchConfig, d_in: int | None = None) -> dict:
    d = d_in or cfg.d_model
    dh = cfg.dh
    ks = jax.random.split(key, 4)
    p = {
        "wq": linear_init(ks[0], d, cfg.n_heads * dh),
        "wk": linear_init(ks[1], d, cfg.n_kv_heads * dh),
        "wv": linear_init(ks[2], d, cfg.n_kv_heads * dh),
        "wo": linear_init(ks[3], cfg.n_heads * dh, cfg.d_model,
                          spec=P("model", None)),
    }
    if cfg.qk_norm:
        p["q_norm"] = norm_init(dh)
        p["k_norm"] = norm_init(dh)
    return p


def _attn_scores_block(q, k, scale, softcap):
    # q: (B,C,Hkv,G,dh)  k: (B,S,Hkv,dh) -> (B,Hkv,G,C,S)
    s = jnp.einsum("bchgd,bshd->bhgcs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    return s


def attention(
    q: jax.Array,                # (B, Sq, H, dh)
    k: jax.Array,                # (B, Sk, Hkv, dh)
    v: jax.Array,                # (B, Sk, Hkv, dh)
    *,
    causal_offset: jax.Array | int = 0,   # absolute position of q[0];
                                          # (B,) => per-sequence (decode)
    window: jax.Array | int = 0,          # 0 => full causal
    softcap: float = 0.0,
    chunk: int = 1024,
    kv_valid_len: jax.Array | None = None,  # for decode with preallocated
                                            # cache; (B,) => per-sequence
    causal: bool = True,                    # False: bidirectional / cross-attn
) -> jax.Array:
    b, sq, h, dh = q.shape
    sk = k.shape[1]
    hkv = k.shape[2]
    g = h // hkv
    scale = dh ** -0.5
    qr = q.reshape(b, sq, hkv, g, dh)
    kpos = jnp.arange(sk)
    window = jnp.asarray(window)
    kv_limit = sk if kv_valid_len is None else jnp.asarray(kv_valid_len)
    offset = jnp.asarray(causal_offset)

    def block(qc, qpos):
        # qpos: (C,) or, for per-sequence decode offsets, (B, C)
        s = _attn_scores_block(qc, k, scale, softcap)      # (B,Hkv,G,C,Sk)
        if causal:
            cmask = kpos <= qpos[..., None]
            in_window = jnp.where(window > 0,
                                  kpos > qpos[..., None] - window, True)
        else:
            cmask = jnp.ones(qpos.shape + (sk,), bool)
            in_window = True
        valid = (kpos[None, :] < kv_limit[:, None, None]
                 if getattr(kv_limit, "ndim", 0) == 1 else kpos < kv_limit)
        mask = cmask & in_window & valid            # (C, Sk) or (B, C, Sk)
        if mask.ndim == 2:
            mask = mask[None]
        s = jnp.where(mask[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgcs,bshd->bchgd", p, v.astype(jnp.float32))
        return o.reshape(b, -1, h, dh).astype(q.dtype)

    if sq <= chunk:
        return block(qr, offset[..., None] + jnp.arange(sq))

    assert sq % chunk == 0, f"Sq={sq} not divisible by attn chunk {chunk}"
    nc = sq // chunk

    def chunk_fn(i):
        qc = jax.lax.dynamic_slice_in_dim(qr, i * chunk, chunk, axis=1)
        qpos = offset[..., None] + i * chunk + jnp.arange(chunk)
        return block(qc, qpos)

    out = jax.lax.map(chunk_fn, jnp.arange(nc))            # (nc,B,C,H,dh)
    return jnp.moveaxis(out, 0, 1).reshape(b, sq, h, dh)


# ---------------------------------------------------------------------------
# Packed MixFP4 KV cache (the decode_32k traffic term; docs/serving.md)
# ---------------------------------------------------------------------------
# Per-tensor scale shared by every KV row.  Rows are quantized incrementally
# (one per decode step), so the level-2 scale cannot be data-dependent — it
# must be identical for rows written at different times.  RoPE'd K and raw V
# are O(1); with s32=1 the per-block E4M3 scale alone covers blockmaxes up
# to 6*448 = 2688 before clipping, the same headroom the paper's per-tensor
# rule (max|X|/2688) grants a tensor whose absmax IS 2688.
KV_SCALE32 = 1.0


def quantize_kv_rows(kv: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Quantize KV rows into the wire format under the shared KV_SCALE32.

    kv (..., dh) -> (payload (..., dh//2) u8, scales (..., dh//16) u8) via
    the fused Pallas row quantizer; 1-D g=16 blocks along the head dim.
    Replaces the historical ``serving.quantize_kv`` loose triple (which
    derived a per-call scale32 and so could not serve incremental writes).
    """
    from repro.kernels import ops  # deferred: kernels import core

    shape = kv.shape
    flat = kv.reshape(-1, shape[-1]).astype(jnp.float32)
    payload, scales, _ = _whole_on_each_device(
        lambda a: ops.quantize_rows(a, scale32=KV_SCALE32), flat)
    return (payload.reshape(*shape[:-1], shape[-1] // 2),
            scales.reshape(*shape[:-1], shape[-1] // 16))


def _map_slot_arrays(fn, *trees):
    """tree.map over cache trees whose leaves may be QTensors: ``fn`` is
    applied to dense leaves and to QTensor payload/scales children, while
    scale32 (no per-slot batch axis — it is shared by construction) passes
    through from the first tree untouched."""
    is_qt = lambda x: isinstance(x, qtensor.QTensor)

    def one(leaf, *rest):
        if is_qt(leaf):
            return qtensor.QTensor(
                fn(leaf.payload, *[r.payload for r in rest]),
                fn(leaf.scales, *[r.scales for r in rest]),
                leaf.scale32, leaf.method, leaf.layout, leaf.shape,
                leaf.dtype)
        return fn(leaf, *rest)

    return jax.tree.map(one, *trees, is_leaf=is_qt)


def slot_take(cache, slot):
    """Slice slot ``slot``'s batch row (axis 1 of every (L, B, ...) cache
    leaf) into a batch-1 cache — the single-slot prefill view."""
    return _map_slot_arrays(
        lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1), cache)


def slot_put(cache, small, slot):
    """Scatter a batch-1 cache (from :func:`slot_take` + a prefill) back
    into slot ``slot`` — only that batch row is written, so an admission is
    invisible to every other slot without any snapshot/restore."""
    return _map_slot_arrays(
        lambda a, s: jax.lax.dynamic_update_slice_in_dim(
            a, s.astype(a.dtype), slot, axis=1), cache, small)


def _attn_packed_cached(q, knew, vnew, kv_cache, cache_len, window,
                        cfg: ArchConfig):
    """Attention over the packed QTensor KV cache.

    Decode (s == 1): quantize the new K/V row, scatter its packed bytes
    into the cache at each slot's position, and run the fused Pallas
    decode-attention kernel straight over the packed arrays — no dense
    bf16 copy of the cache is ever materialized.

    Prefill (s > 1, scalar ``cache_len``): quantize all prompt rows at
    once, write the packed slab, and attend over the *dequantized* rows —
    bit-identical values to what later decode steps will read back, so a
    batched prefill and a token-by-token replay see the same quantized
    history.
    """
    from repro.kernels import ops  # deferred: kernels import core

    b, s, _, _ = q.shape
    ck, cv = kv_cache
    cl = jnp.asarray(cache_len)
    kp, ks = quantize_kv_rows(knew)
    vp, vs = quantize_kv_rows(vnew)
    if s == 1:
        cl_vec = cl if cl.ndim else jnp.broadcast_to(cl, (b,))
        rows = jnp.arange(b)
        ckp = ck.payload.at[rows, cl_vec].set(kp[:, 0])
        cks = ck.scales.at[rows, cl_vec].set(ks[:, 0])
        cvp = cv.payload.at[rows, cl_vec].set(vp[:, 0])
        cvs = cv.scales.at[rows, cl_vec].set(vs[:, 0])
        o = _whole_on_each_device(
            lambda *a: ops.attn_decode_packed(
                *a[:6], window=a[6], softcap=cfg.softcap_attn,
                k_scale32=a[7], v_scale32=a[8]),
            q[:, 0], ckp, cks, cvp, cvs, cl_vec + 1, jnp.asarray(window),
            ck.scale32, cv.scale32)
        o = o[:, None].astype(q.dtype)
    else:
        assert cl.ndim == 0, \
            "packed-KV prefill requires a scalar cache_len (whole-prompt " \
            "writes start at one position)"
        ckp = jax.lax.dynamic_update_slice_in_dim(ck.payload, kp, cl, axis=1)
        cks = jax.lax.dynamic_update_slice_in_dim(ck.scales, ks, cl, axis=1)
        cvp = jax.lax.dynamic_update_slice_in_dim(cv.payload, vp, cl, axis=1)
        cvs = jax.lax.dynamic_update_slice_in_dim(cv.scales, vs, cl, axis=1)
        k = qtensor.from_packed_rows(ckp, cks, ck.scale32).dequantize()
        v = qtensor.from_packed_rows(cvp, cvs, cv.scale32).dequantize()
        o = attention(q, k, v, causal_offset=cl, window=window,
                      softcap=cfg.softcap_attn, chunk=cfg.attn_chunk,
                      kv_valid_len=cl + s)
    new_k = qtensor.QTensor(ckp, cks, ck.scale32, ck.method, ck.layout,
                            ck.shape, ck.dtype)
    new_v = qtensor.QTensor(cvp, cvs, cv.scale32, cv.method, cv.layout,
                            cv.shape, cv.dtype)
    return o, (new_k, new_v)


def _attn_paged_cached(q, knew, vnew, kv_cache, cache_len, block_tables,
                       window, cfg: ArchConfig):
    """Attention over the *paged* packed KV pool (serving.kvpool).

    The cache children are physical page slabs (P, page_len, Hkv, ...) and
    ``block_tables`` (B, max_pages) int32 maps each sequence's logical page
    order to slab rows — logical position ``t`` lives at
    ``(block_tables[b, t // page_len], t % page_len)``.

    Decode (s == 1): quantize the new row, scatter its packed bytes through
    the page translation, and run the paged flash kernel over the slabs +
    table.  Inactive lanes scatter into page 0 (the pool's trash page,
    where zeroed table rows point); every read of it is masked by lengths.

    Prefill (s > 1, scalar ``cache_len`` = the suffix start): scatter all
    rows through the translation, then gather the sequence's pages into the
    logical (1, max_pages*page_len, ...) view and attend over the
    *dequantized* rows exactly as the fixed-slot prefill does — gathered
    bytes equal the fixed path's in every valid position and junk rows
    beyond ``kv_valid_len`` are masked identically, so the logits are
    bitwise the fixed path's.
    """
    from repro.kernels import ops  # deferred: kernels import core

    b, s, _, _ = q.shape
    ck, cv = kv_cache
    page_len = ck.payload.shape[1]
    cl = jnp.asarray(cache_len)
    kp, ks = quantize_kv_rows(knew)
    vp, vs = quantize_kv_rows(vnew)
    if s == 1:
        cl_vec = cl if cl.ndim else jnp.broadcast_to(cl, (b,))
        rows = jnp.arange(b)
        phys = block_tables[rows, cl_vec // page_len]
        off = cl_vec % page_len
        ckp = ck.payload.at[phys, off].set(kp[:, 0])
        cks = ck.scales.at[phys, off].set(ks[:, 0])
        cvp = cv.payload.at[phys, off].set(vp[:, 0])
        cvs = cv.scales.at[phys, off].set(vs[:, 0])
        o = ops.attn_decode_paged(
            q[:, 0], ckp, cks, cvp, cvs, block_tables, cl_vec + 1,
            window=window, softcap=cfg.softcap_attn,
            k_scale32=ck.scale32, v_scale32=cv.scale32)
        o = o[:, None].astype(q.dtype)
    else:
        assert cl.ndim == 0, \
            "paged prefill requires a scalar cache_len (the suffix start)"
        assert b == 1, "paged prefill is a single-request view (b == 1)"
        pos = cl + jnp.arange(s)
        phys = block_tables[0, pos // page_len]
        off = pos % page_len
        ckp = ck.payload.at[phys, off].set(kp[0])
        cks = ck.scales.at[phys, off].set(ks[0])
        cvp = cv.payload.at[phys, off].set(vp[0])
        cvs = cv.scales.at[phys, off].set(vs[0])

        def logical(a):  # (P, page_len, Hkv, x) -> (1, S_logical, Hkv, x)
            g = a[block_tables[0]]
            return g.reshape(1, -1, *g.shape[2:])

        k = qtensor.from_packed_rows(
            logical(ckp), logical(cks), ck.scale32).dequantize()
        v = qtensor.from_packed_rows(
            logical(cvp), logical(cvs), cv.scale32).dequantize()
        o = attention(q, k, v, causal_offset=cl, window=window,
                      softcap=cfg.softcap_attn, chunk=cfg.attn_chunk,
                      kv_valid_len=cl + s)
    new_k = qtensor.QTensor(ckp, cks, ck.scale32, ck.method, ck.layout,
                            ck.shape, ck.dtype)
    new_v = qtensor.QTensor(cvp, cvs, cv.scale32, cv.method, cv.layout,
                            cv.shape, cv.dtype)
    return o, (new_k, new_v)


def attn_apply(p: dict, x: jax.Array, ctx: Ctx, cfg: ArchConfig, *,
               positions: jax.Array, window, kv_cache=None,
               cache_len=None, causal: bool = True, block_tables=None,
               ) -> tuple[jax.Array, tuple | None]:
    """Full attention sub-layer.  When ``kv_cache=(K, V)`` is given, new K/V
    are written at ``cache_len`` and attention runs over the cache (decode).
    A cache of packed QTensors routes through the fused packed-KV path;
    with ``block_tables`` the QTensors are paged pool slabs and writes/reads
    go through the page translation (serving.kvpool)."""
    b, s, _ = x.shape
    dh = cfg.dh
    q = qlinear(x, p["wq"], ctx, 0).reshape(b, s, cfg.n_heads, dh)
    knew = qlinear(x, p["wk"], ctx, 1).reshape(b, s, cfg.n_kv_heads, dh)
    vnew = qlinear(x, p["wv"], ctx, 2).reshape(b, s, cfg.n_kv_heads, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        knew = rms_norm(knew, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    knew = apply_rope(knew, positions, cfg.rope_theta)

    # TP layout for attention: shard heads over 'model' when divisible;
    # otherwise shard K/V over the *key sequence* (flash-decoding style:
    # every chip scores a key slice, softmax reductions psum over model).
    # Indivisible explicit constraints would trigger involuntary full
    # rematerialisation in SPMD, so never emit those.
    m = _active_mesh()
    msize = m.shape["model"] if (m is not None and "model" in m.axis_names) else 1
    heads_div = cfg.n_heads % msize == 0 and cfg.n_kv_heads % msize == 0
    if heads_div:
        q = shard(q, "data", None, "model", None)
        knew = shard(knew, "data", None, "model", None)
        vnew = shard(vnew, "data", None, "model", None)
    else:
        q = shard(q, "data", None, None, None)
        if knew.shape[1] % msize == 0:
            knew = shard(knew, "data", "model", None, None)
            vnew = shard(vnew, "data", "model", None, None)

    if kv_cache is not None and isinstance(kv_cache[0], qtensor.QTensor):
        if block_tables is not None:
            o, new_cache = _attn_paged_cached(
                q, knew, vnew, kv_cache, cache_len, block_tables, window,
                cfg)
        else:
            o, new_cache = _attn_packed_cached(
                q, knew, vnew, kv_cache, cache_len, window, cfg)
        out = qlinear(o.reshape(b, s, cfg.n_heads * dh), p["wo"], ctx, 3)
        return out, new_cache

    new_cache = None
    if kv_cache is None:
        k, v = knew, vnew
        causal_offset = 0
        kv_valid = None
    else:
        ck, cv = kv_cache
        cl = jnp.asarray(cache_len)
        if cl.ndim == 1:
            # per-sequence cache positions (continuous batching: each slot
            # decodes at its own length) — single-token scatter per row
            assert s == 1, "per-sequence cache_len requires single-token steps"
            rows = jnp.arange(b)
            ck = ck.at[rows, cl].set(knew[:, 0].astype(ck.dtype))
            cv = cv.at[rows, cl].set(vnew[:, 0].astype(cv.dtype))
        else:
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, knew.astype(ck.dtype), cache_len, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, vnew.astype(cv.dtype), cache_len, axis=1)
        k, v = ck, cv
        causal_offset = cl
        kv_valid = cl + s
        new_cache = (ck, cv)

    o = attention(q, k, v, causal_offset=causal_offset, window=window,
                  softcap=cfg.softcap_attn, chunk=cfg.attn_chunk,
                  kv_valid_len=kv_valid, causal=causal)
    out = qlinear(o.reshape(b, s, cfg.n_heads * dh), p["wo"], ctx, 3)
    return out, new_cache


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeLU / GeGLU)
# ---------------------------------------------------------------------------
def mlp_init(key, cfg: ArchConfig, d_ff: int | None = None,
             d_in: int | None = None) -> dict:
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w_up": linear_init(ks[0], d, f),
         "w_down": linear_init(ks[1], f, cfg.d_model, spec=P("model", None))}
    if cfg.mlp_type in ("swiglu", "geglu"):
        p["w_gate"] = linear_init(ks[2], d, f)
    return p


def mlp(p: dict, x: jax.Array, ctx: Ctx, cfg: ArchConfig) -> jax.Array:
    mid = (None,) * (x.ndim - 2)  # rank-adaptive: (B,S,D) or (T,D) inputs
    up = qlinear(x, p["w_up"], ctx, 4)
    up = shard(up, "data", *mid, "model")
    if cfg.mlp_type == "swiglu":
        gate = jax.nn.silu(qlinear(x, p["w_gate"], ctx, 5))
        h = shard(gate, "data", *mid, "model") * up
    elif cfg.mlp_type == "geglu":
        gate = jax.nn.gelu(qlinear(x, p["w_gate"], ctx, 5))
        h = shard(gate, "data", *mid, "model") * up
    else:  # gelu
        h = jax.nn.gelu(up)
    return qlinear(h, p["w_down"], ctx, 6)


# ---------------------------------------------------------------------------
# Shared LM head / loss
# ---------------------------------------------------------------------------
def lm_logits(x: jax.Array, embed: jax.Array, softcap: float = 0.0,
              vocab: int | None = None) -> jax.Array:
    """Tied-embedding LM head (bf16 inputs, f32 logits), optional softcap.
    ``vocab`` slices off the TP padding rows of the embedding."""
    logits = jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                        embed.astype(jnp.float32))
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    if vocab is not None and logits.shape[-1] != vocab:
        logits = logits[..., :vocab]
    return logits


def xent_loss(logits: jax.Array, labels: jax.Array,
              valid_vocab: int | None = None) -> jax.Array:
    """Mean next-token cross entropy; labels < 0 are masked.

    ``valid_vocab`` masks TP-padding columns out of the logsumexp so the
    loss over a padded-vocab logits tensor is exact — logits stay
    vocab-sharded all the way into the reduction (no all-gather)."""
    if valid_vocab is not None and logits.shape[-1] != valid_vocab:
        col = jnp.arange(logits.shape[-1])
        logits = jnp.where(col < valid_vocab, logits, -1e30)
    mask = (labels >= 0).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum((lse - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def fused_lm_loss(x: jax.Array, embed: jax.Array, labels: jax.Array,
                  softcap: float, valid_vocab: int,
                  chunk: int = 1024) -> jax.Array:
    """Sequence-chunked LM head + cross entropy (never materialises the full
    (B, S, V) logits — the dominant temp of big-vocab training).  The scan
    body is rematerialised in the backward pass, bounding live logits to one
    chunk."""
    b, s, d = x.shape
    chunk = min(chunk, s)
    if s % chunk:
        chunk = s  # fall back (smoke shapes)
    nc = s // chunk

    def body(acc, i):
        xc = jax.lax.dynamic_slice_in_dim(x, i * chunk, chunk, axis=1)
        lc = jax.lax.dynamic_slice_in_dim(labels, i * chunk, chunk, axis=1)
        logits = lm_logits(xc, embed, softcap)
        logits = shard(logits, "data", None, "model")
        if logits.shape[-1] != valid_vocab:
            col = jnp.arange(logits.shape[-1])
            logits = jnp.where(col < valid_vocab, logits, -1e30)
        mask = (lc >= 0).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        nll, cnt = acc
        return (nll + jnp.sum((lse - gold) * mask),
                cnt + jnp.sum(mask)), None

    body_fn = jax.checkpoint(body) if nc > 1 else body
    (nll, cnt), _ = jax.lax.scan(
        body_fn, (jnp.float32(0.0), jnp.float32(0.0)), jnp.arange(nc))
    return nll / jnp.maximum(cnt, 1.0)


# ---------------------------------------------------------------------------
# Model registry
# ---------------------------------------------------------------------------
def build_model(cfg: ArchConfig):
    """Return the module implementing ``cfg.family``; each module exposes
    init / forward / loss / init_cache / prefill / decode_step."""
    from repro.models import encdec, mamba, transformer

    if cfg.family in ("dense", "moe", "vlm"):
        return transformer.TransformerLM(cfg)
    if cfg.family in ("ssm", "hybrid"):
        return mamba.MambaLM(cfg)
    if cfg.family == "encdec":
        return encdec.EncDecLM(cfg)
    raise ValueError(f"unknown family {cfg.family!r}")

"""Cost-model kernel autotuner for the packed Pallas hot path.

Replaces the divisor-only tile rule the GEMM dispatcher used through PR 4
(``largest tile <= cap that divides the padded dim``), which collapses to
16-wide tiles the moment a padded dimension has no large divisor — e.g.
``Np = 272 = 17 * 16`` served every projection with ``bn = 16`` grid tiles,
two orders of magnitude more grid cells than the hardware wants.  This is
exactly the "promise vs. performance" gap of naive FP4 tiling: the kernel
is bandwidth-bound, and tiny tiles multiply both the per-cell launch
overhead and the number of times the activation panel is re-streamed.

The tuner scores ``(bm, bn, bk)`` candidates with an arithmetic-intensity /
VMEM-footprint model of the double-buffered GEMM in
``kernels/mixfp4_gemm.py`` and returns a :class:`TileChoice` that also
carries the padded problem dims — K and N are padded *up* to tile multiples
(the dispatcher zero-pads the packed operands; zero payload/scale bytes
decode to exact zeros) the same way M already was, so no dimension ever
degrades to 16-wide tiles.

Contracts the selection upholds (tested in ``tests/test_tuning.py``):

* every choice's :func:`vmem_footprint` fits :data:`VMEM_BUDGET`,
* a padded dim >= 128 never gets a tile below 128 lanes (``MIN_WIDE``),
* every tile is one the TPU compiler accepts: bn a multiple of 128 and bk
  of 256, or the whole dimension,
* ``bk`` is chosen independently of N, so a column-parallel shard of a
  weight keeps the single-device K tiling — the bitwise-identity contract
  of ``qmm_sharded`` (docs/sharding.md) survives autotuning,
* activation rows round up a fixed ``bm`` ladder (:func:`round_up_rows`),
  so continuous-batching batch-size wobble (m = 3, 4, 5, ...) lands on one
  padded M and reuses one compiled kernel instead of re-jitting per m.

Choices are cached per ``(path, padded shape)`` in a process-level table
and come from this code alone.

This module is pure Python on purpose (no jax import): it is consulted at
trace time from ``core/qtensor.py`` and ``kernels/mixfp4_attn.py`` and must
never add dispatch-path work or import cycles.
"""
from __future__ import annotations

import dataclasses
import threading

__all__ = [
    "TileChoice",
    "select_tiles",
    "select_attn_key_block",
    "round_up_rows",
    "divisor_tile",
    "vmem_footprint",
    "attn_vmem_footprint",
    "VMEM_BUDGET",
    "MIN_WIDE",
    "BM_LADDER",
    "clear_cache",
    "cache_info",
]

_G = 16          # paper block size g (scale granularity)

# ---------------------------------------------------------------------------
# Hardware model (v5e-class).  Absolute numbers only matter relative to each
# other — the tuner ranks candidates, it does not predict wall time.
# ---------------------------------------------------------------------------
PEAK_FLOPS = 1.97e14     # bf16 MXU FLOP/s
HBM_BW = 8.1e11          # HBM bytes/s
VPU_OPS = 2.0e13         # elementwise op/s (Fig. 9 decode + quant prologue)
VMEM_BYTES = 16 * 2 ** 20
VMEM_BUDGET = int(VMEM_BYTES * 0.70)   # leave headroom for Mosaic spills
DMA_SETUP_S = 1.0e-6     # per-transfer latency: favors fat slabs
GRID_CELL_S = 1.5e-6     # per grid cell launch/bookkeeping overhead

# Mosaic DMAs whole (8, 128) tiles: a K slab is bk//2 lanes of the split
# activation halves and weight payload rows, an N slab bn lanes, so a tile
# is a whole multiple of 128 lanes (bk of 256) or the whole dimension.
MIN_WIDE = 128           # padded dims never collapse below 128 lanes
BM_LADDER = (8, 16, 32, 64, 128)
_BN_CHOICES = (128, 256, 512)
_BK_CHOICES = (256, 512)
_SINGLE_TILE_CAP = 512   # whole-dim single tile allowed up to this width

# VPU op counts per value (coarse: selects/shifts/multiplies per element)
_DECODE_OPS = 12.0       # Fig. 9 dual-codebook decode
_QUANT_OPS = 40.0        # fused prologue: dual-candidate quantize + argmin

_PATHS = ("w4a16", "w4a4", "w4a4_fused")


def _pad(d: int, t: int) -> int:
    return -(-d // t) * t


def round_up_rows(m: int, cap: int = 128) -> int:
    """Activation-row tile from the fixed ladder: the smallest ladder entry
    >= m (``cap`` for larger m).  Rounding m up this ladder inside the
    dispatcher is what stops decode-batch wobble re-jitting the kernel per
    distinct small m."""
    for b in BM_LADDER:
        if m <= b:
            return min(b, cap)
    return cap


def divisor_tile(dim: int, cap: int, mult: int = _G) -> int:
    """The historical PR-1 rule (largest divisor <= cap), kept verbatim for
    the tuner A/B benchmark: this is what collapses prime-ish dims to
    ``mult``-wide tiles."""
    t = min(cap, dim)
    t -= t % mult
    while t > mult and dim % t:
        t -= mult
    return max(t, mult) if dim % mult == 0 else 1


@dataclasses.dataclass(frozen=True)
class TileChoice:
    """A tuned GEMM tiling plus the padded problem it runs on."""

    bm: int
    bn: int
    bk: int
    m_pad: int
    k_pad: int
    n_pad: int

    def astuple(self) -> tuple:
        return dataclasses.astuple(self)


# ---------------------------------------------------------------------------
# VMEM footprint of one grid cell of the double-buffered kernel
# ---------------------------------------------------------------------------
def _x_slab_bytes(path: str, bm: int, bk: int) -> int:
    if path == "w4a16":
        return bm * bk * 2                      # bf16 rows
    if path == "w4a4":
        return bm * (bk // 2 + bk // _G)        # packed payload + scales
    return bm * bk * 4                          # fused: f32 rows


def _w_slab_bytes(bk: int, bn: int) -> int:
    return bk * bn // 2 + (bk // _G) * max(bn // _G, 1)


def vmem_footprint(path: str, bm: int, bn: int, bk: int) -> int:
    """Live VMEM model for one grid cell of the streamed GEMM: two slots per
    double-buffered operand, the decoded bf16 x/w tiles, the f32
    accumulator, the (pipeline double-buffered) output block, and — on the
    fused path — the quantizer's candidate working set (~3 extra f32 copies
    of the x tile, mirroring ``mixfp4_quant._pick_bm``'s budget rule)."""
    x = 2 * _x_slab_bytes(path, bm, bk)
    w = 2 * _w_slab_bytes(bk, bn)
    decoded = bk * bn * 2 + bm * bk * 2
    acc = bm * bn * 4
    out = 2 * bm * bn * 4
    quant = 3 * bm * bk * 4 if path == "w4a4_fused" else 0
    return x + w + decoded + acc + out + quant


# ---------------------------------------------------------------------------
# Cost model
# ---------------------------------------------------------------------------
def _x_value_bytes(path: str) -> float:
    if path == "w4a16":
        return 2.0
    if path == "w4a4":
        return 0.5 + 1.0 / _G
    return 4.0


def _n_dmas(path: str) -> int:
    # transfers per K step: x slab(s) + weight payload + weight scales
    return 4 if path == "w4a4" else 3


def _cell_time(path: str, m: int, kp: int, np_: int,
               bm: int, bn: int, bk: int) -> float:
    """Predicted time of the whole GEMM under (bm, bn, bk): max of the
    compute, HBM-traffic and VPU (decode/quant) roofs, plus grid-cell and
    DMA-setup overheads.  Padding waste enters through the padded dims;
    re-padding a weight operand that does not already sit on the tile grid
    costs one extra packed copy (read + write)."""
    mp, kpp, npp = _pad(m, bm), _pad(kp, bk), _pad(np_, bn)
    gm, gn, nk = mp // bm, npp // bn, kpp // bk

    flops = 2.0 * mp * kpp * npp
    w_bytes = kpp * npp / 2 + (kpp // _G) * (npp // _G)
    x_traffic = mp * kpp * _x_value_bytes(path) * gn   # x re-streamed per j
    w_traffic = w_bytes * gm                           # w re-streamed per i
    out_traffic = mp * npp * 4.0
    pad_copy = 2.0 * w_bytes if (kpp != kp or npp != np_) else 0.0
    traffic = x_traffic + w_traffic + out_traffic + pad_copy

    decode = _DECODE_OPS * kpp * npp * gm          # weight decode per revisit
    if path == "w4a4":
        decode += _DECODE_OPS * mp * kpp * gn      # packed-x decode per j
    elif path == "w4a4_fused":
        decode += _QUANT_OPS * mp * kpp * gn       # in-kernel quant per j

    t = max(flops / PEAK_FLOPS, traffic / HBM_BW, decode / VPU_OPS)
    t += gm * gn * GRID_CELL_S
    t += gm * gn * nk * _n_dmas(path) * DMA_SETUP_S
    return t


def _tile_candidates(dim: int, choices: tuple) -> list:
    """Tile widths for a (16-aligned) padded dim: below ``MIN_WIDE`` the
    single exact tile; otherwise the >= MIN_WIDE ladder entries plus the
    whole dim as a single tile when it is not absurdly wide (kills padding
    waste for e.g. 272 = 17*16)."""
    if dim < MIN_WIDE:
        return [dim]
    cands = [c for c in choices if MIN_WIDE <= c <= max(dim, MIN_WIDE)]
    if dim <= _SINGLE_TILE_CAP and dim not in cands:
        cands.append(dim)
    return cands or [dim]


def _select_bk(path: str, m: int, kp: int, bm: int) -> int:
    """K tile, scored against a NOMINAL N so the choice is independent of
    the real N — a column-parallel shard must keep the single-device K
    tiling for the ``qmm_sharded`` bitwise contract."""
    n_nom, bn_nom = 256, 128
    # the fused kernel and the packed composition share this choice, so
    # feasibility uses the larger (fused: f32 slab + quant workspace)
    # footprint of the two
    feas = "w4a4_fused" if path == "w4a4" else path
    best, best_t = None, None
    for bk in _tile_candidates(kp, _BK_CHOICES):
        if vmem_footprint(feas, bm, MIN_WIDE, bk) > VMEM_BUDGET:
            continue
        t = _cell_time(path, m, kp, n_nom, bm, bn_nom, bk)
        if best_t is None or t < best_t - 1e-12 or \
                (abs(t - best_t) <= 1e-12 and bk > best):
            best, best_t = bk, t
    if best is None:
        raise ValueError(f"no K tile of {kp} fits the VMEM budget at "
                         f"bm={bm}")
    return best


def _select_bn(path: str, m: int, kp: int, np_: int, bm: int, bk: int) -> int:
    feas = "w4a4_fused" if path == "w4a4" else path
    best, best_t = None, None
    for bn in _tile_candidates(np_, _BN_CHOICES):
        if vmem_footprint(feas, bm, bn, bk) > VMEM_BUDGET:
            continue
        t = _cell_time(path, m, kp, np_, bm, bn, bk)
        if best_t is None or t < best_t - 1e-12 or \
                (abs(t - best_t) <= 1e-12 and bn > best):
            best, best_t = bn, t
    if best is None:
        raise ValueError(f"no N tile of {np_} fits the VMEM budget at "
                         f"bm={bm}, bk={bk}")
    return best


# ---------------------------------------------------------------------------
# Process-level cache
# ---------------------------------------------------------------------------
_LOCK = threading.Lock()
_CACHE: dict = {}
_STATS = {"hits": 0, "misses": 0}


def clear_cache():
    with _LOCK:
        _CACHE.clear()
        _STATS["hits"] = _STATS["misses"] = 0


def cache_info() -> dict:
    with _LOCK:
        return {"entries": len(_CACHE), **_STATS}


# ---------------------------------------------------------------------------
# Public selection entry points
# ---------------------------------------------------------------------------
def select_tiles(path: str, m: int, kp: int, np_: int) -> TileChoice:
    """Tiles + padded dims for a GEMM of ``m`` activation rows against a
    packed ``(kp, np_)`` weight grid (both already 16-aligned).

    ``path`` is one of ``"w4a16"`` (dense rows), ``"w4a4"`` (packed rows)
    or ``"w4a4_fused"`` (dense rows quantized in the kernel prologue) —
    the two W4A4 spellings share one cache entry so the fused kernel and
    the two-dispatch composition always run the SAME grid, which is what
    makes them bitwise-comparable."""
    if path not in _PATHS:
        raise ValueError(f"unknown path {path!r} (expected one of {_PATHS})")
    if kp % _G or np_ % _G:
        raise ValueError(f"select_tiles expects 16-aligned packed dims, "
                         f"got K={kp} N={np_}")
    group = "w4a4" if path.startswith("w4a4") else "w4a16"
    bm = round_up_rows(m)
    mp = _pad(m, bm)
    key = (group, mp, kp, np_)
    with _LOCK:
        hit = _CACHE.get(key)
        if hit is not None:
            _STATS["hits"] += 1
            return hit
    bk = _select_bk(group, mp, kp, bm)
    bn = _select_bn(group, mp, kp, np_, bm, bk)
    ch = TileChoice(bm, bn, bk, mp, _pad(kp, bk), _pad(np_, bn))
    with _LOCK:
        _STATS["misses"] += 1
        _CACHE[key] = ch
    return ch


_ATTN_BS_CHOICES = (16, 32, 64, 128, 256, 512)


def attn_vmem_footprint(bs: int, hkv: int, dh: int) -> int:
    """VMEM model for one key block of the packed decode-attention kernel:
    double-buffered packed K and V slabs (payload + scale bytes) plus the
    decoded f32 blocks and flash state."""
    packed = bs * hkv * (dh // 2 + dh // _G)
    decoded = bs * hkv * dh * 4
    return 2 * 2 * packed + 2 * decoded + 4 * hkv * dh * 4


def select_attn_key_block(s: int, hkv: int, dh: int) -> int:
    """Key-block rows per flash-decoding step of ``mixfp4_attn``: the
    largest block that fits the VMEM model and doesn't waste more in S
    padding than it saves in per-block overhead."""
    s = max(int(s), 1)
    key = ("attn", s, hkv, dh)
    with _LOCK:
        hit = _CACHE.get(key)
        if hit is not None:
            _STATS["hits"] += 1
            return hit
    best, best_t = _G, None
    bytes_per_row = hkv * (dh // 2 + dh // _G) * 2     # packed K + V
    for bs in _ATTN_BS_CHOICES:
        if attn_vmem_footprint(bs, hkv, dh) > VMEM_BUDGET:
            continue
        sp = _pad(s, bs)
        t = sp * bytes_per_row / HBM_BW \
            + (sp // bs) * GRID_CELL_S \
            + _DECODE_OPS * 2 * sp * hkv * dh / VPU_OPS
        if best_t is None or t < best_t - 1e-15 or \
                (abs(t - best_t) <= 1e-15 and bs > best):
            best, best_t = bs, t
    with _LOCK:
        _STATS["misses"] += 1
        _CACHE[key] = best
    return best

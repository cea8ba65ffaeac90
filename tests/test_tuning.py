"""Cost-model autotuner unit tests (kernels/tuning.py).

Pins the contracts the GEMM dispatcher and the sharded path rely on:
VMEM budget respected for every choice, padded dims never collapse below
64 lanes, bk independent of N (the qmm_sharded column-parallel bitwise
contract), the bm row ladder (decode-batch churn fix), the process
cache, and the refusal of a shape no tile fits.
"""
import pytest

from repro.kernels import tuning


@pytest.fixture(autouse=True)
def _fresh_cache():
    tuning.clear_cache()
    yield
    tuning.clear_cache()


SHAPES = [
    (1, 64, 64), (1, 4096, 4096), (4, 256, 256), (17, 272, 272),
    (32, 304, 4096), (128, 8192, 1024), (513, 16384, 16384),
    (1, 16, 16), (7, 48, 32), (64, 1088, 272),
]


@pytest.mark.parametrize("path", ["w4a16", "w4a4", "w4a4_fused"])
@pytest.mark.parametrize("m,kp,np_", SHAPES)
def test_vmem_budget_respected(path, m, kp, np_):
    ch = tuning.select_tiles(path, m, kp, np_)
    assert tuning.vmem_footprint(path, ch.bm, ch.bn, ch.bk) \
        <= tuning.VMEM_BUDGET, ch
    # tiles divide the padded problem exactly
    assert ch.m_pad % ch.bm == 0 and ch.m_pad >= m
    assert ch.k_pad % ch.bk == 0 and ch.k_pad >= kp
    assert ch.n_pad % ch.bn == 0 and ch.n_pad >= np_
    assert ch.bk % 16 == 0 and ch.bn % 16 == 0


@pytest.mark.parametrize("kp,np_", [(272, 272), (304, 304), (272, 4096),
                                    (4096, 304), (1088, 1088),
                                    (4112, 4112)])
def test_padded_dims_never_collapse_below_64(kp, np_):
    """Prime-ish K/N (17*16, 19*16, 257*16...) used to degrade to 16-wide
    divisor tiles; the cost model must keep every tile >= 64 lanes when
    the dim itself is >= 64."""
    for path in ("w4a16", "w4a4"):
        ch = tuning.select_tiles(path, 8, kp, np_)
        assert ch.bk >= tuning.MIN_WIDE, (path, ch)
        assert ch.bn >= tuning.MIN_WIDE, (path, ch)
        # and the divisor rule really did collapse (documents the fix)
        if kp % 64:
            assert tuning.divisor_tile(kp, 256) == 16


def test_round_shapes_unpadded():
    """Round dims must not pick up padding (no regression on the shapes
    the divisor rule already handled well)."""
    for m, kp, np_ in [(4, 256, 256), (32, 512, 512), (128, 4096, 4096)]:
        ch = tuning.select_tiles("w4a16", m, kp, np_)
        assert ch.k_pad == kp and ch.n_pad == np_, ch


def test_bk_independent_of_n():
    """The K tile must not depend on N: a column-parallel shard (local
    N = global N / shards) keeps the single-device K tiling, which is what
    makes qmm_sharded bitwise-identical to the single-device kernel."""
    for path in ("w4a16", "w4a4"):
        bks = {tuning.select_tiles(path, 8, 4096, n).bk
               for n in (64, 256, 272, 2048, 16384)}
        assert len(bks) == 1, (path, bks)


def test_row_ladder_kills_decode_batch_churn():
    """m = 3, 4, 5 ... must land on ONE padded M (and so one compiled
    kernel); the ladder is the fixed BM_LADDER."""
    assert tuning.round_up_rows(1) == 8
    assert tuning.round_up_rows(3) == 8
    assert tuning.round_up_rows(9) == 16
    assert tuning.round_up_rows(100) == 128
    assert tuning.round_up_rows(1000) == 128
    pads = {tuning.select_tiles("w4a16", m, 256, 256).m_pad
            for m in (1, 2, 3, 5, 8)}
    assert pads == {8}, pads
    # above the cap, M pads to the cap multiple
    ch = tuning.select_tiles("w4a16", 300, 256, 256)
    assert ch.bm == 128 and ch.m_pad == 384


def test_w4a4_and_fused_share_tiles():
    """The fused prologue and the two-dispatch composition must run the
    SAME grid — that is what makes them bitwise-comparable."""
    a = tuning.select_tiles("w4a4", 5, 272, 144)
    b = tuning.select_tiles("w4a4_fused", 5, 272, 144)
    assert a == b
    info = tuning.cache_info()
    assert info["entries"] == 1 and info["hits"] == 1, info


def test_unknown_path_and_unaligned_dims_rejected():
    with pytest.raises(ValueError, match="unknown path"):
        tuning.select_tiles("w8a8", 1, 256, 256)
    with pytest.raises(ValueError, match="16-aligned"):
        tuning.select_tiles("w4a16", 1, 250, 256)


def test_process_cache_serves_repeat_lookups():
    a = tuning.select_tiles("w4a16", 4, 272, 272)
    bs = tuning.select_attn_key_block(1000, 2, 64)
    info0 = tuning.cache_info()
    assert tuning.select_tiles("w4a16", 4, 272, 272) == a
    assert tuning.select_attn_key_block(1000, 2, 64) == bs
    info1 = tuning.cache_info()
    # both lookups were served from the cache, not re-scored
    assert info1["hits"] == info0["hits"] + 2
    assert info1["misses"] == info0["misses"]


@pytest.mark.parametrize("path", ["w4a16", "w4a4", "w4a4_fused"])
def test_no_feasible_tile_raises(path, monkeypatch):
    """A shape whose every candidate tile overflows VMEM is refused; a
    16-wide fallback tile would not compile for the TPU."""
    monkeypatch.setattr(tuning, "VMEM_BUDGET", 1)
    with pytest.raises(ValueError, match="fits the VMEM budget"):
        tuning.select_tiles(path, 8, 2304, 2048)


def test_attn_key_block_contracts():
    """Key-block sizing: multiple-of-16, VMEM model respected, small S
    never gets a block wider than its own padding would justify."""
    for s, hkv, dh in [(16, 2, 64), (128, 2, 64), (4096, 8, 128),
                      (32768, 2, 256)]:
        bs = tuning.select_attn_key_block(s, hkv, dh)
        assert bs % 16 == 0
        assert tuning.attn_vmem_footprint(bs, hkv, dh) <= tuning.VMEM_BUDGET
    assert tuning.select_attn_key_block(16, 2, 64) <= 32
    # long caches get large blocks (fewer flash steps)
    assert tuning.select_attn_key_block(32768, 2, 64) >= 256

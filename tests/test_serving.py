"""Serving engine over packed QTensor weights: end-to-end decode through
qmm -> interpret-mode Pallas kernels, weight packing invariants, the empty-
prompt regression, and packed-weight checkpointing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import qtensor
from repro.core.qgemm import QuantConfig
from repro.models.base import (ArchConfig, PROJECTION_KEYS, build_model,
                               pack_projections)
from repro.serving.engine import Request, ServeEngine


@pytest.fixture(scope="module")
def small_cfg():
    return ArchConfig(name="serve-test", family="dense", n_layers=2,
                      d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
                      vocab=64, attn_chunk=64,
                      quant=QuantConfig(method="mixfp4"))


@pytest.fixture(scope="module")
def engine(small_cfg):
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return ServeEngine(small_cfg, params, batch_size=2, max_len=32)


def _collect_projection_leaves(node, out):
    if isinstance(node, dict):
        for k, v in node.items():
            if k in PROJECTION_KEYS:
                out.append((k, v))
            else:
                _collect_projection_leaves(v, out)
    return out


def test_projections_held_only_as_qtensors(engine):
    """Acceptance: projection weights live ONLY as packed QTensors — no
    dense bf16 copies retained in the engine's parameter tree."""
    leaves = _collect_projection_leaves(engine.params, [])
    assert leaves, "no projection leaves found"
    for k, v in leaves:
        assert isinstance(v, qtensor.QTensor), f"{k} is dense: {type(v)}"
        assert v.payload.dtype == jnp.uint8
    assert engine.compression > 3.5  # ~3.97x for 2-D 16x16 tiles vs bf16
    assert engine.packed_bytes < engine.dense_bytes / 3.5


def test_serve_end_to_end_from_packed_weights(engine):
    rng = np.random.RandomState(0)
    reqs = [Request(uid=i, prompt=rng.randint(0, 64, 4).astype(np.int32),
                    max_new_tokens=3) for i in range(2)]
    for r in reqs:
        assert engine.add_request(r)
    tokens = []
    for _ in range(8):
        out = engine.step()
        tokens.extend(out)
        if not any(s is not None for s in engine.slots):
            break
    assert len(tokens) == 6  # 2 requests x 3 new tokens
    assert all(0 <= t < 64 for _, t in tokens)


def test_empty_prompt_rejected(small_cfg):
    """Regression: an empty prompt used to hit UnboundLocalError on
    `logits` inside _prefill_slot; it must be rejected up front."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(1))
    eng = ServeEngine(small_cfg, params, batch_size=1, max_len=8,
                      pack_weights=False)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.add_request(Request(uid=0, prompt=np.zeros((0,), np.int32)))
    # the slot must not have been consumed by the failed admission
    assert eng.slots == [None]


def test_packed_weights_checkpoint_roundtrip(small_cfg, engine, tmp_path):
    engine.save_weights(str(tmp_path))
    model = build_model(small_cfg)
    params2, _ = model.init(jax.random.PRNGKey(42))  # different weights
    eng2 = ServeEngine(small_cfg, params2, batch_size=2, max_len=32)
    eng2.load_weights(str(tmp_path))
    a = jax.tree.leaves(engine.params)
    b = jax.tree.leaves(eng2.params)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    # and it still decodes
    assert eng2.add_request(
        Request(uid=9, prompt=np.array([1, 2], np.int32), max_new_tokens=1))
    assert len(eng2.step()) == 1


def test_ssm_family_serves_from_packed_weights():
    """PROJECTION_KEYS covers the Mamba blocks too (in/x/dt/out_proj):
    the SSM family also decodes through qmm from packed QTensors."""
    cfg = ArchConfig(name="ssm-serve", family="ssm", n_layers=2, d_model=64,
                     vocab=64, ssm_state=8, ssm_expand=2,
                     quant=QuantConfig(method="mixfp4"))
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(3))
    eng = ServeEngine(cfg, params, batch_size=1, max_len=16)
    assert eng.packed_bytes > 0 and eng.compression > 3.0
    leaves = _collect_projection_leaves(eng.params, [])
    assert any(isinstance(v, qtensor.QTensor) for _, v in leaves)
    eng.add_request(Request(uid=0, prompt=np.array([3, 4, 5], np.int32),
                            max_new_tokens=2))
    out = eng.step() + eng.step()
    assert len(out) == 2 and all(0 <= t < 64 for _, t in out)


def _serve_one(eng, prompt, n_new):
    eng.add_request(Request(uid=0, prompt=np.asarray(prompt, np.int32),
                            max_new_tokens=n_new))
    toks = []
    while any(s is not None for s in eng.slots):
        toks.extend(t for _, t in eng.step())
    return toks


def test_slot_reuse_no_contamination(small_cfg):
    """Regression: a request admitted into a freed slot used to prefill at
    the dead request's cache offset and attend to its stale K/V.  The slot
    must now reset to position 0, so a reused-slot serve is bit-identical
    to a fresh engine."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(7))
    eng = ServeEngine(small_cfg, params, batch_size=1, max_len=32)
    _serve_one(eng, [9, 8, 7, 6, 5], 6)        # occupies + frees slot 0
    reused = _serve_one(eng, [1, 2, 3], 4)     # admitted into the freed slot

    fresh_eng = ServeEngine(small_cfg, params, batch_size=1, max_len=32)
    fresh = _serve_one(fresh_eng, [1, 2, 3], 4)
    assert reused == fresh


def test_concurrent_requests_match_solo(small_cfg):
    """Regression: per-slot cache positions — slot B's prefill must not
    clobber slot A's written K/V, and each slot decodes at its own length.

    Checks the exact invariant (A's written cache region is untouched by
    B's prefill) plus numeric equivalence of the concurrent next-token
    logits against solo engines; greedy token chains are NOT compared —
    a random-weight model is chaotic enough that benign batch-shape
    compile differences (~1e-7) can flip an argmax."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(11))
    eng = ServeEngine(small_cfg, params, batch_size=2, max_len=32)
    pa = np.array([3, 1, 4, 1, 5], np.int32)
    pb = np.array([2, 7, 1, 8, 2, 8, 1], np.int32)   # different length too
    ra = Request(uid=0, prompt=pa, max_new_tokens=4)
    rb = Request(uid=1, prompt=pb, max_new_tokens=4)
    assert eng.add_request(ra)
    ka = np.asarray(eng.cache["k"])[:, 0, :len(pa)].copy()
    va = np.asarray(eng.cache["v"])[:, 0, :len(pa)].copy()
    assert eng.add_request(rb)
    assert list(eng.lengths) == [len(pa), len(pb)]
    # B's prefill wrote only slot 1 (and slot 0's not-yet-valid position)
    np.testing.assert_array_equal(
        ka, np.asarray(eng.cache["k"])[:, 0, :len(pa)])
    np.testing.assert_array_equal(
        va, np.asarray(eng.cache["v"])[:, 0, :len(pa)])

    # next-token logits of the concurrent batch == solo engines' (each slot
    # attends only to its own history, at its own cache position); feed a
    # fixed probe token so the check is independent of prefill argmaxes
    logits2, _ = eng._decode(eng.params, jnp.array([7, 7], jnp.int32),
                             eng.cache, jnp.asarray(eng.lengths))
    for prompt, row in ((pa, 0), (pb, 1)):
        solo = ServeEngine(small_cfg, params, batch_size=1, max_len=32)
        solo.add_request(Request(uid=9, prompt=prompt, max_new_tokens=4))
        logits1, _ = solo._decode(solo.params, jnp.array([7], jnp.int32),
                                  solo.cache, jnp.asarray(solo.lengths))
        np.testing.assert_allclose(np.asarray(logits2[row]),
                                   np.asarray(logits1[0]), atol=1e-4)


def test_engine_emits_greedy_continuation(small_cfg):
    """Regression: the prefill's argmax used to be fed back but never
    emitted, shifting the output stream by one token.  The engine's stream
    must equal the raw greedy continuation of the prompt."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(21))
    prompt = [9, 8, 7]
    eng = ServeEngine(small_cfg, params, batch_size=1, max_len=32)
    got = _serve_one(eng, prompt, 4)

    ref_eng = ServeEngine(small_cfg, params, batch_size=1, max_len=32)
    cache, want = ref_eng.cache, []
    seq = list(prompt)
    for t in range(len(prompt) + 3):
        tok = seq[t] if t < len(seq) else want[-1]
        logits, cache = ref_eng._decode(
            ref_eng.params, jnp.array([tok], jnp.int32), cache,
            jnp.array([t], jnp.int32))
        if t >= len(prompt) - 1:
            want.append(int(jnp.argmax(logits[0])))
    assert got == want


def test_admission_invisible_to_active_ssm_slot():
    """Regression: Mamba's recurrent h/conv state advances for EVERY batch
    row each decode step, so another slot's prefill used to irreversibly
    corrupt an active slot's state (dummy token-0 steps are not overwritten
    like KV rows).  The engine must snapshot/restore other active slots
    around a prefill — an admission is bitwise-invisible to batchmates."""
    cfg = ArchConfig(name="ssm-serve2", family="ssm", n_layers=2, d_model=64,
                     vocab=64, ssm_state=8, ssm_expand=2,
                     quant=QuantConfig(method="mixfp4"))
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(3))
    eng = ServeEngine(cfg, params, batch_size=2, max_len=16)
    ra = Request(uid=0, prompt=np.array([3, 4, 5], np.int32),
                 max_new_tokens=8)
    eng.add_request(ra)
    eng.step()                                   # A is mid-generation
    before = {k: np.asarray(v).copy() for k, v in eng.cache.items()}
    eng.add_request(Request(uid=1, prompt=np.array([9, 8, 7, 6], np.int32),
                            max_new_tokens=2))
    for k in before:
        # slot 0's rows (batch axis 1) must be untouched by B's admission
        np.testing.assert_array_equal(
            before[k][:, 0], np.asarray(eng.cache[k])[:, 0],
            err_msg=f"cache[{k}] slot 0 mutated by another admission")


def test_request_exceeding_max_len_rejected(small_cfg):
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(small_cfg, params, batch_size=1, max_len=8,
                      pack_weights=False)
    with pytest.raises(ValueError, match="max_len"):
        eng.add_request(Request(uid=0, prompt=np.arange(6, dtype=np.int32),
                                max_new_tokens=4))
    assert eng.slots == [None]
    # boundary: the final token is never fed back, so prompt 6 + 3 new fits
    # exactly in max_len=8 (highest position written is 7)
    fits = Request(uid=1, prompt=np.arange(6, dtype=np.int32),
                   max_new_tokens=3)
    assert eng.add_request(fits)
    while any(s is not None for s in eng.slots):
        eng.step()
    assert len(fits.generated) == 3


def test_cold_restore_recomputes_stats(small_cfg, tmp_path):
    """A cold engine (pack_weights=False) that load_weights a packed
    checkpoint must report the restored tree's real storage stats."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    warm = ServeEngine(small_cfg, params, batch_size=1, max_len=16)
    warm.save_weights(str(tmp_path))
    cold = ServeEngine(small_cfg, params, batch_size=1, max_len=16,
                       pack_weights=False)
    assert cold.packed_bytes == 0 and cold.compression == 1.0
    cold.load_weights(str(tmp_path))
    assert cold.packed_bytes == warm.packed_bytes
    assert cold.dense_bytes == warm.dense_bytes
    assert cold.compression == pytest.approx(warm.compression)


def test_moe_family_serves_from_packed_experts():
    """Scan-stacked MoE expert weights ((n_layers, E, K, N), 4-D) must be
    packed too — the engine's 'projections held only as QTensors' contract
    covers the dominant weight term of a MoE model."""
    from repro import configs
    cfg = configs.smoke_config("qwen3-moe-30b-a3b").replace(
        quant=QuantConfig(method="mixfp4"))
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(5))
    eng = ServeEngine(cfg, params, batch_size=1, max_len=16)
    leaves = dict(_collect_projection_leaves(eng.params, []))
    for name in ("w_up", "w_gate", "w_down"):
        assert isinstance(leaves[name], qtensor.QTensor), name
    # expert stacks carry (n_layers, E) lead dims on the packed children
    assert leaves["w_up"].payload.ndim == 4
    out = _serve_one(eng, [3, 4, 5], 2)
    assert len(out) == 2 and all(0 <= t < cfg.vocab for t in out)


def test_step_on_unprefilled_request_raises(small_cfg):
    """Regression: ``_next`` used to be injected dynamically by the prefill,
    so step() on a slot holding a hand-constructed (never-admitted) request
    died with AttributeError.  It is now a real Request field; the engine
    raises a clear error instead."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(small_cfg, params, batch_size=1, max_len=8,
                      pack_weights=False)
    req = Request(uid=0, prompt=np.array([1, 2], np.int32))
    assert req._next is None  # declared field, not injected
    eng.slots[0] = req        # bypass add_request on purpose
    with pytest.raises(RuntimeError, match="never .*prefilled"):
        eng.step()


@pytest.mark.parametrize("kv_quant", [None, "mixfp4"])
def test_batched_prefill_bitwise_matches_replay(small_cfg, kv_quant):
    """The batched prefill_slot entry must write bit-identical cache rows
    and produce the identical first token as the historical token-by-token
    decode replay — for the bf16 cache AND the packed cache (whose rows
    quantize identically whether written one at a time or as one slab)."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(7))
    prompt = np.array([9, 8, 7, 3, 1], np.int32)

    eng = ServeEngine(small_cfg, params, batch_size=2, max_len=32,
                      kv_quant=kv_quant)
    eng.add_request(Request(uid=0, prompt=prompt, max_new_tokens=1))
    batched_first = eng.slots[0]._next if eng.slots[0] else \
        eng.step()[0][1]  # max_new=1: slot may already have been freed
    batched_cache = eng.cache

    replay = ServeEngine(small_cfg, params, batch_size=2, max_len=32,
                         kv_quant=kv_quant)
    cache = replay.model.reset_slot(replay.cache, 0)
    lengths = np.zeros((2,), np.int32)
    logits = None
    for tok in prompt:
        toks = np.zeros((2,), np.int32)
        toks[0] = tok
        logits, cache = replay._decode(replay.params, jnp.asarray(toks),
                                       cache, jnp.asarray(lengths.copy()))
        lengths[0] += 1
    replay_first = int(jnp.argmax(logits[0]))

    assert batched_first == replay_first

    def slot0_rows(c):
        rows = {}
        for name, leaf in c.items():
            if isinstance(leaf, qtensor.QTensor):
                rows[f"{name}.payload"] = \
                    np.asarray(leaf.payload)[:, 0, :len(prompt)]
                rows[f"{name}.scales"] = \
                    np.asarray(leaf.scales)[:, 0, :len(prompt)]
            else:
                rows[name] = np.asarray(leaf)[:, 0, :len(prompt)]
        return rows

    got, want = slot0_rows(batched_cache), slot0_rows(cache)
    for name in want:
        np.testing.assert_array_equal(got[name], want[name],
                                      err_msg=f"cache[{name}] rows differ")


def test_packed_kv_cache_is_qtensor_and_small(small_cfg):
    """Acceptance: with kv_quant='mixfp4' the engine's KV cache is held as
    1-D-blocked QTensors (uint8 wire children, never a dense bf16 tensor)
    at <= 0.3x the bf16 cache bytes."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    packed = ServeEngine(small_cfg, params, batch_size=2, max_len=32,
                         kv_quant="mixfp4")
    dense = ServeEngine(small_cfg, params, batch_size=2, max_len=32)
    for name in ("k", "v"):
        leaf = packed.cache[name]
        assert isinstance(leaf, qtensor.QTensor)
        assert leaf.payload.dtype == jnp.uint8
        assert leaf.scales.dtype == jnp.uint8
        assert isinstance(leaf.layout, qtensor.BlockLayout1D)
    assert packed.kv_cache_bytes() <= 0.3 * dense.kv_cache_bytes()
    # decode leaves the cache packed (still QTensors after steps)
    packed.add_request(Request(uid=0, prompt=np.array([1, 2, 3], np.int32),
                               max_new_tokens=2))
    packed.step()
    packed.step()
    assert isinstance(packed.cache["k"], qtensor.QTensor)


def test_packed_kv_tokens_match_bf16_engine(small_cfg):
    """Greedy output streams of the packed-KV engine vs the bf16-cache
    engine (same packed weights).  KV quantization error is real but small;
    on these pinned seeds/prompts the argmax chain is identical."""
    model = build_model(small_cfg)
    for seed, prompt in [(0, [3, 1, 4, 1, 5]), (5, [9, 8, 7]),
                         (2, [2, 7, 1, 8])]:
        params, _ = model.init(jax.random.PRNGKey(seed))
        streams = {}
        for kv in ("bf16", "mixfp4"):
            eng = ServeEngine(small_cfg, params, batch_size=1, max_len=32,
                              kv_quant=kv)
            streams[kv] = _serve_one(eng, prompt, 5)
        assert streams["mixfp4"] == streams["bf16"], (seed, streams)


def test_packed_kv_slot_reuse_no_contamination(small_cfg):
    """Slot reuse on the packed cache: reset_slot zeroes the slot's packed
    bytes, so a reused-slot serve is bit-identical to a fresh engine."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(7))
    eng = ServeEngine(small_cfg, params, batch_size=1, max_len=32,
                      kv_quant="mixfp4")
    _serve_one(eng, [9, 8, 7, 6, 5], 6)        # occupies + frees slot 0
    reused = _serve_one(eng, [1, 2, 3], 4)     # admitted into the freed slot

    fresh = ServeEngine(small_cfg, params, batch_size=1, max_len=32,
                        kv_quant="mixfp4")
    assert reused == _serve_one(fresh, [1, 2, 3], 4)


def test_packed_kv_concurrent_matches_solo(small_cfg):
    """Per-slot packed decode at ragged lengths: the concurrent batch's
    next-token logits equal solo packed engines' (each slot reads only its
    own packed rows, at its own cache position)."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(11))
    eng = ServeEngine(small_cfg, params, batch_size=2, max_len=32,
                      kv_quant="mixfp4")
    pa = np.array([3, 1, 4, 1, 5], np.int32)
    pb = np.array([2, 7, 1, 8, 2, 8, 1], np.int32)
    eng.add_request(Request(uid=0, prompt=pa, max_new_tokens=4))
    eng.add_request(Request(uid=1, prompt=pb, max_new_tokens=4))
    logits2, _ = eng._decode(eng.params, jnp.array([7, 7], jnp.int32),
                             eng.cache, jnp.asarray(eng.lengths))
    for prompt, row in ((pa, 0), (pb, 1)):
        solo = ServeEngine(small_cfg, params, batch_size=1, max_len=32,
                           kv_quant="mixfp4")
        solo.add_request(Request(uid=9, prompt=prompt, max_new_tokens=4))
        logits1, _ = solo._decode(solo.params, jnp.array([7], jnp.int32),
                                  solo.cache, jnp.asarray(solo.lengths))
        np.testing.assert_allclose(np.asarray(logits2[row]),
                                   np.asarray(logits1[0]), atol=1e-4)


def test_packed_kv_odd_dh_block_count():
    """dh=48 (three 16-lane blocks per row) serves through the fused
    packed-KV path end to end."""
    cfg = ArchConfig(name="serve-dh48", family="dense", n_layers=2,
                     d_model=96, n_heads=2, n_kv_heads=2, d_ff=128,
                     vocab=64, attn_chunk=64,
                     quant=QuantConfig(method="mixfp4"))
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(1))
    eng = ServeEngine(cfg, params, batch_size=1, max_len=16,
                      kv_quant="mixfp4")
    assert eng.cache["k"].payload.shape[-1] == 24   # dh//2
    assert eng.cache["k"].scales.shape[-1] == 3     # dh//16
    out = _serve_one(eng, [3, 4, 5], 3)
    assert len(out) == 3 and all(0 <= t < 64 for t in out)


def test_packed_kv_validation():
    """kv_quant gating: non-transformer families and dh % 16 != 0 are
    rejected up front with clear errors."""
    ssm = ArchConfig(name="ssm-kv", family="ssm", n_layers=1, d_model=64,
                     vocab=64, ssm_state=8, ssm_expand=2,
                     quant=QuantConfig(method="mixfp4"))
    model = build_model(ssm)
    params, _ = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="kv_quant"):
        ServeEngine(ssm, params, batch_size=1, max_len=8, kv_quant="mixfp4")

    dense = ArchConfig(name="dh-odd", family="dense", n_layers=1,
                       d_model=48, n_heads=2, n_kv_heads=2, d_ff=64,
                       vocab=64, quant=QuantConfig(method="mixfp4"))
    m2 = build_model(dense)
    p2, _ = m2.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="head_dim"):
        ServeEngine(dense, p2, batch_size=1, max_len=8, kv_quant="mixfp4")

    with pytest.raises(ValueError, match="kv_quant"):
        ServeEngine(dense, p2, batch_size=1, max_len=8, kv_quant="int3")


def test_ssm_prefill_awkward_prompt_length():
    """Regression: the batched SSM prefill runs the chunked selective scan,
    which requires p_len % ssm_chunk == 0 once p_len exceeds the chunk —
    prefill_slot must fall back to one unchunked block for awkward prompt
    lengths (the replay path decoded at s=1 and never hit this)."""
    cfg = ArchConfig(name="ssm-chunk", family="ssm", n_layers=2, d_model=64,
                     vocab=64, ssm_state=8, ssm_expand=2, ssm_chunk=4,
                     quant=QuantConfig(method="mixfp4"))
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(3))
    eng = ServeEngine(cfg, params, batch_size=1, max_len=16)
    out = _serve_one(eng, [3, 1, 4, 1, 5, 9], 2)   # 6 % 4 != 0
    assert len(out) == 2 and all(0 <= t < 64 for t in out)


def test_single_prefill_dispatch_per_admission(small_cfg):
    """Acceptance: an admission costs exactly ONE prefill jit dispatch (the
    historical replay cost O(prompt_len) decode dispatches)."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(small_cfg, params, batch_size=2, max_len=32)
    for uid, prompt in enumerate(([5, 4, 3, 2, 1, 0], [1, 2])):
        eng.add_request(Request(uid=uid, prompt=np.asarray(prompt, np.int32),
                                max_new_tokens=2))
    assert eng.admissions == 2
    assert eng.prefill_dispatches == eng.admissions


# ---------------------------------------------------------------------------
# W4A4 serving (act_quant="mixfp4"): quantized activations through the
# full FP4 MMA path (docs/serving.md)
# ---------------------------------------------------------------------------
def _family_cfg(family: str):
    """Tiny per-family configs + a pinned seed each (the oracle equality
    below is an argmax-chain comparison, so seeds are pinned the same way
    test_packed_kv_tokens_match_bf16_engine pins them)."""
    if family == "dense":
        return ArchConfig(name="w4a4-dense", family="dense", n_layers=2,
                          d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
                          vocab=64, attn_chunk=64,
                          quant=QuantConfig(method="mixfp4")), 0
    if family == "moe":
        from repro import configs
        return configs.smoke_config("qwen3-moe-30b-a3b").replace(
            quant=QuantConfig(method="mixfp4")), 5
    if family == "ssm":
        return ArchConfig(name="w4a4-ssm", family="ssm", n_layers=2,
                          d_model=64, vocab=64, ssm_state=8, ssm_expand=2,
                          quant=QuantConfig(method="mixfp4")), 3
    if family == "hybrid":
        return ArchConfig(name="w4a4-hyb", family="hybrid", n_layers=2,
                          d_model=64, vocab=64, n_heads=2, n_kv_heads=2,
                          d_ff=128, ssm_state=8, ssm_expand=2,
                          ssm_version=2, ssm_head_dim=32, attn_period=2,
                          attn_chunk=64,
                          quant=QuantConfig(method="mixfp4")), 2
    raise ValueError(family)


@pytest.mark.parametrize("family", ["dense", "moe", "ssm", "hybrid"])
def test_w4a4_stream_matches_dequantize_oracle(family):
    """Each W4A4 spelling is pinned against its wire-compatible oracle,
    per model family: the fused per-row path ('mixfp4') against the
    'mixfp4-2pass-rowscale' composition (quantize_rows(per_row=True) ->
    W4A4 kernel — SAME per-row bytes, independent dispatch structure), and
    the legacy per-tensor composition ('mixfp4-2pass') against the
    dequantize-then-W4A16 oracle ('mixfp4-qdq' — SAME per-tensor bytes,
    decoded in the kernel's factored-scale form through the W4A16 kernel).
    The per-row and per-tensor pairs quantize with DIFFERENT scale32
    policies, so only within-pair equality is exact."""
    cfg, seed = _family_cfg(family)
    params, _ = build_model(cfg).init(jax.random.PRNGKey(seed))
    streams = {}
    for aq in ("mixfp4", "mixfp4-2pass-rowscale", "mixfp4-2pass",
               "mixfp4-qdq"):
        eng = ServeEngine(cfg, params, batch_size=1, max_len=16,
                          act_quant=aq)
        streams[aq] = _serve_one(eng, [3, 4, 5], 4)
    assert streams["mixfp4"] == streams["mixfp4-2pass-rowscale"], \
        (family, streams)
    assert streams["mixfp4-2pass"] == streams["mixfp4-qdq"], \
        (family, streams)
    assert all(0 <= t < cfg.vocab for t in streams["mixfp4"])


def test_w4a4_concurrent_ragged_matches_oracle(small_cfg):
    """W4A4 continuous batching at per-slot ragged lengths: each slot's
    activations quantize at its own cache position, and the concurrent
    fused streams equal the per-row composition oracle's bitwise (same
    admissions, same batch shapes, same per-row wire bytes).  The old
    per-tensor batch-coupling caveat that used to live here is gone: a
    row's scale32 is derived from that row alone, so ragged batchmates
    cannot move anyone's bytes (see
    test_w4a4_stream_invariant_to_batchmates for the direct pin)."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(11))
    pa = np.array([3, 1, 4, 1, 5], np.int32)
    pb = np.array([2, 7, 1, 8, 2, 8, 1], np.int32)   # ragged lengths

    def both(aq):
        eng = ServeEngine(small_cfg, params, batch_size=2, max_len=32,
                          act_quant=aq)
        eng.add_request(Request(uid=0, prompt=pa, max_new_tokens=4))
        eng.add_request(Request(uid=1, prompt=pb, max_new_tokens=4))
        out = {0: [], 1: []}
        while any(s is not None for s in eng.slots):
            for uid, tok in eng.step():
                out[uid].append(tok)
        return out

    got, want = both("mixfp4"), both("mixfp4-2pass-rowscale")
    assert got == want
    assert all(len(v) == 4 for v in got.values()), got


def test_w4a4_stream_invariant_to_batchmates(small_cfg):
    """THE serving-level batch-independence pin: the same request, served
    under act_quant='mixfp4' next to two DIFFERENT batchmates (different
    content and length, one with a deliberately outlier-heavy prompt
    embedding path), emits the bitwise-identical token stream.  Under the
    old per-tensor activation scale this diverged — the batchmate's
    activation range moved the shared scale32 and with it the victim's
    wire bytes."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(13))
    victim = np.array([3, 1, 4, 1, 5], np.int32)

    def stream_of_victim(mate_prompt):
        eng = ServeEngine(small_cfg, params, batch_size=2, max_len=32,
                          act_quant="mixfp4")
        eng.add_request(Request(uid=0, prompt=victim, max_new_tokens=5))
        eng.add_request(Request(uid=1, prompt=mate_prompt,
                                max_new_tokens=5))
        out = {0: [], 1: []}
        while any(s is not None for s in eng.slots):
            for uid, tok in eng.step():
                out[uid].append(tok)
        assert len(out[0]) == 5
        return out[0]

    a = stream_of_victim(np.array([2, 7, 1, 8], np.int32))
    b = stream_of_victim(np.array([60, 61, 62, 63, 1, 2, 3], np.int32))
    assert a == b, (a, b)


def test_w4a4_composes_with_packed_kv(small_cfg):
    """The two packed hot paths compose: act_quant='mixfp4' +
    kv_quant='mixfp4' serves projections W4A4 AND reads the packed KV
    cache through the fused attention kernel, still matching the oracle
    run under the same cache format."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(7))
    streams = {}
    for aq in ("mixfp4", "mixfp4-2pass-rowscale"):
        eng = ServeEngine(small_cfg, params, batch_size=1, max_len=32,
                          kv_quant="mixfp4", act_quant=aq)
        assert isinstance(eng.cache["k"], qtensor.QTensor)
        streams[aq] = _serve_one(eng, [9, 8, 7], 5)
    assert streams["mixfp4"] == streams["mixfp4-2pass-rowscale"], streams


def test_w4a4_validation(small_cfg):
    """act_quant gating: unknown values and the packless combination are
    rejected up front with clear errors."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="act_quant"):
        ServeEngine(small_cfg, params, batch_size=1, max_len=8,
                    act_quant="int4")
    with pytest.raises(ValueError, match="packed weights"):
        ServeEngine(small_cfg, params, batch_size=1, max_len=8,
                    act_quant="mixfp4", pack_weights=False)


def test_pack_projections_skips_non_projection_leaves():
    tree = {"layers": {"wq": jnp.ones((2, 32, 32)),
                       "ln_attn": jnp.ones((2, 32)),
                       "embed_like": jnp.ones((64, 32))},
            "embed": jnp.ones((64, 32))}
    packed, pb, db = pack_projections(tree)
    assert isinstance(packed["layers"]["wq"], qtensor.QTensor)
    assert isinstance(packed["layers"]["ln_attn"], jax.Array)
    assert isinstance(packed["embed"], jax.Array)
    assert pb > 0 and db == 2 * 32 * 32 * 2


# ---------------------------------------------------------------------------
# Fused quantize+GEMM serving (act_quant="mixfp4" -> one dispatch per
# projection) and prompt-length bucketing (PR-5)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("family", ["dense", "moe"])
def test_w4a4_fused_stream_matches_two_dispatch(family):
    """act_quant='mixfp4' (fused per-row prologue) must emit the IDENTICAL
    token stream to 'mixfp4-2pass-rowscale' (quantize_rows(per_row=True)
    -> W4A4 kernel): the kernels are bitwise-identical, so even the argmax
    chain cannot diverge."""
    cfg, seed = _family_cfg(family)
    params, _ = build_model(cfg).init(jax.random.PRNGKey(seed))
    streams = {}
    for aq in ("mixfp4", "mixfp4-2pass-rowscale"):
        eng = ServeEngine(cfg, params, batch_size=1, max_len=16,
                          act_quant=aq)
        streams[aq] = _serve_one(eng, [3, 4, 5], 4)
    assert streams["mixfp4"] == streams["mixfp4-2pass-rowscale"], \
        (family, streams)


def test_w4a4_fused_one_dispatch_per_projection(small_cfg):
    """Tracing one decode step must count exactly ONE GEMM-path kernel
    entry per projection on the fused path, and two on the composition."""
    from repro.kernels import ops
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(0))

    def counts(aq):
        eng = ServeEngine(small_cfg, params, batch_size=2, max_len=16,
                          act_quant=aq)
        toks = jnp.zeros((2,), jnp.int32)
        lens = jnp.zeros((2,), jnp.int32)
        with ops.count_dispatches() as c:
            jax.eval_shape(
                lambda p, t, cc, l: eng.model.decode_step(
                    p, t, eng.ctx, cc, l),
                eng.params, toks, eng.cache, lens)
        return dict(c)

    c16 = counts(None)           # W4A16: one kernel per projection
    n_proj = sum(c16.values())
    assert set(c16) == {"gemm_w4a16"} and n_proj > 0, c16
    c_fused = counts("mixfp4")
    assert c_fused == {"gemm_w4a4_fused": n_proj}, (c_fused, n_proj)
    c_two = counts("mixfp4-2pass")
    assert c_two == {"quantize_rows": n_proj, "gemm_w4a4": n_proj}, c_two
    # the per-row composition has the same dispatch structure as the
    # legacy per-tensor one — only the scale32 shape differs
    c_rs = counts("mixfp4-2pass-rowscale")
    assert c_rs == {"quantize_rows": n_proj, "gemm_w4a4": n_proj}, c_rs


def test_prefill_bucketing_stream_bitwise_and_compile_reuse(small_cfg):
    """Bucketed prefill (W4A16 engine) must emit bitwise-identical streams
    to the unbucketed engine, while nearby prompt lengths share ONE
    compiled prefill shape (the compile-cache counters prove it)."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(3))
    prompts = [[3, 4, 5], [1, 2, 3, 4, 5], [9, 8, 7, 6], [2, 2]]

    def run(buckets):
        eng = ServeEngine(small_cfg, params, batch_size=2, max_len=32,
                          prefill_buckets=buckets)
        streams = {}
        pending = [Request(uid=i, prompt=np.array(p, np.int32),
                           max_new_tokens=4)
                   for i, p in enumerate(prompts)]
        while pending or any(s is not None for s in eng.slots):
            while pending and eng.add_request(pending[0]):
                pending.pop(0)
            for uid, tok in eng.step():
                streams.setdefault(uid, []).append(tok)
        return streams, eng

    bucketed, eng_b = run("pow2-64")
    plain, eng_p = run("off")
    assert bucketed == plain
    # lengths 3, 5, 4, 2 all bucket to 8: one compiled shape, three hits
    assert eng_b.prefill_compiles == 1, eng_b.prefill_compiles
    assert eng_b.prefill_cache_hits == 3, eng_b.prefill_cache_hits
    assert eng_p.prefill_compiles == 4   # one shape per distinct length
    assert eng_b.prefill_dispatches == eng_b.admissions == 4


def test_w4a4_act_rht_stream_matches_composition(small_cfg):
    """Serve-time RHT (``act_rht=True``): the fused engine (in-prologue
    grouped FWHT) must emit bitwise the per-row two-dispatch engine's
    stream — both rotate activations with ``hadamard.serve_signs`` on the
    packed K grid and serve weights rotated with the SAME signs at pack
    time, so the transform cancels in every dot product."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(19))
    streams = {}
    for aq in ("mixfp4", "mixfp4-2pass-rowscale"):
        eng = ServeEngine(small_cfg, params, batch_size=1, max_len=16,
                          act_quant=aq, act_rht=True)
        assert eng.act_rht
        streams[aq] = _serve_one(eng, [3, 4, 5], 4)
    assert streams["mixfp4"] == streams["mixfp4-2pass-rowscale"], streams
    # and the validation surface: RHT rides the per-row modes only
    with pytest.raises(ValueError, match="act_rht"):
        ServeEngine(small_cfg, params, batch_size=1, max_len=16,
                    act_quant="mixfp4-2pass", act_rht=True)
    with pytest.raises(ValueError, match="act_rht"):
        ServeEngine(small_cfg, params, batch_size=1, max_len=16,
                    act_rht=True)


def test_w4a4_prefill_bucketing_stream_bitwise(small_cfg):
    """Bucketed prefill under act_quant='mixfp4' must emit BITWISE the
    unbucketed engine's streams.  This is the regression the per-row
    activation scale32 fixes: with the old per-tensor scale the bucket's
    zero-padded suffix rows sat in the same amax reduction as the real
    prompt rows, so padding a prompt from 5 to 8 rows could move every
    real row's wire bytes.  Per-row scales make a padded row's existence
    invisible to its neighbours — exact equality, no tolerance."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(3))
    prompts = [[3, 4, 5], [1, 2, 3, 4, 5], [9, 8, 7, 6], [2, 2]]

    def run(buckets):
        eng = ServeEngine(small_cfg, params, batch_size=2, max_len=32,
                          act_quant="mixfp4", prefill_buckets=buckets)
        streams = {}
        pending = [Request(uid=i, prompt=np.array(p, np.int32),
                           max_new_tokens=4)
                   for i, p in enumerate(prompts)]
        while pending or any(s is not None for s in eng.slots):
            while pending and eng.add_request(pending[0]):
                pending.pop(0)
            for uid, tok in eng.step():
                streams.setdefault(uid, []).append(tok)
        return streams, eng

    bucketed, eng_b = run("pow2-64")
    exact, _ = run("off")
    assert bucketed == exact, (bucketed, exact)
    # the buckets really did pad: 3, 5, 4, 2 all share ONE compiled shape
    assert eng_b.prefill_compiles == 1, eng_b.prefill_compiles


def test_w4a4_chunked_prefill_matches_whole_prompt(small_cfg):
    """Chunked prefill under act_quant='mixfp4' must emit BITWISE the
    whole-prompt engine's streams: each chunk's rows quantize with their
    own per-row scales, so neither the chunk boundary placement nor the
    final chunk's padding can move a real row's bytes (with the per-tensor
    scale the per-chunk amax differed from the whole-prompt amax, so
    chunked W4A4 was only same-schedule deterministic)."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(17))
    prompts = [np.array([9, 8, 7, 3, 1], np.int32),
               (np.arange(30, dtype=np.int32) * 7 + 1) % small_cfg.vocab,
               np.array([1, 2], np.int32)]

    def drive(prefill_chunk):
        eng = ServeEngine(small_cfg, params, batch_size=2, max_len=48,
                          act_quant="mixfp4", prefill_chunk=prefill_chunk)
        reqs = [Request(uid=i, prompt=p, max_new_tokens=4)
                for i, p in enumerate(prompts)]
        eng.add_request(reqs[0])
        eng.add_request(reqs[1])      # chunked while req 0 decodes
        eng.step()
        eng.submit(reqs[2])           # queued behind the full batch
        guard = 0
        while any(len(r.generated) < 4 for r in reqs):
            eng.step()
            guard += 1
            assert guard < 200, "engine made no progress"
        return {r.uid: list(r.generated) for r in reqs}

    assert drive(4) == drive(None)


def test_prefill_bucketing_composes_with_packed_kv_and_w4a4(small_cfg):
    """Bucketing + packed KV + fused W4A4 compose: both engines bucket
    identically, so the fused stream still matches the per-row 2pass
    oracle."""
    model = build_model(small_cfg)
    params, _ = model.init(jax.random.PRNGKey(5))
    streams = {}
    for aq in ("mixfp4", "mixfp4-2pass-rowscale"):
        eng = ServeEngine(small_cfg, params, batch_size=1, max_len=32,
                          kv_quant="mixfp4", act_quant=aq,
                          prefill_buckets="pow2-64")
        streams[aq] = _serve_one(eng, [9, 8, 7], 5)
    assert streams["mixfp4"] == streams["mixfp4-2pass-rowscale"], streams


def test_bucket_len_ladder():
    assert ServeEngine.bucket_len(1, 512) == 8
    assert ServeEngine.bucket_len(8, 512) == 8
    assert ServeEngine.bucket_len(9, 512) == 16
    assert ServeEngine.bucket_len(33, 512) == 64
    assert ServeEngine.bucket_len(65, 512) == 128
    assert ServeEngine.bucket_len(130, 512) == 192   # 64-step above 64
    assert ServeEngine.bucket_len(100, 96) == 96     # clamped to max_len


def test_bucketing_rejected_for_recurrent_families():
    """Explicit bucketing on an SSM family must be rejected (padded suffix
    tokens advance the recurrent state); 'auto' silently disables it."""
    cfg = ArchConfig(name="b-ssm", family="ssm", n_layers=2, d_model=64,
                     vocab=64, ssm_state=8, ssm_expand=2,
                     quant=QuantConfig(method="mixfp4"))
    params, _ = build_model(cfg).init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="transformer"):
        ServeEngine(cfg, params, batch_size=1, max_len=16,
                    prefill_buckets="pow2-64")
    eng = ServeEngine(cfg, params, batch_size=1, max_len=16)
    assert eng.prefill_buckets is None
    assert _serve_one(eng, [3, 4, 5], 3)   # still serves fine, unbucketed


def test_act_quant_2pass_accepted_and_validated(small_cfg):
    params, _ = build_model(small_cfg).init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="act_quant"):
        ServeEngine(small_cfg, params, batch_size=1, max_len=8,
                    act_quant="mixfp4-3pass")
    with pytest.raises(ValueError, match="packed weights"):
        ServeEngine(small_cfg, params, batch_size=1, max_len=8,
                    act_quant="mixfp4-2pass", pack_weights=False)
    with pytest.raises(ValueError, match="prefill_buckets"):
        ServeEngine(small_cfg, params, batch_size=1, max_len=8,
                    prefill_buckets="pow3")

# ---------------------------------------------------------------------------
# Paged packed-KV pool: block-table serving, COW prefix caching (PR-6,
# serving.kvpool + docs/serving.md)
# ---------------------------------------------------------------------------
def _run_streams(eng, prompts, n_new=4):
    """Admit prompts as capacity frees up (continuous batching) and
    collect each request's full token stream."""
    pending = [Request(uid=i, prompt=np.asarray(p, np.int32),
                       max_new_tokens=n_new) for i, p in enumerate(prompts)]
    streams = {r.uid: [] for r in pending}
    guard = 0
    while pending or any(s is not None for s in eng.slots):
        while pending and eng.add_request(pending[0]):
            pending.pop(0)
        for uid, tok in eng.step():
            streams[uid].append(tok)
        guard += 1
        assert guard < 500, "engine made no progress"
    return streams


@pytest.mark.parametrize("family", ["dense", "moe", "hybrid"])
def test_paged_stream_matches_fixed(family):
    """Acceptance: the paged engine (block tables + pool + prefix caching)
    must emit token streams IDENTICAL to the fixed-slot packed-KV engine
    for every family with a KV cache.  The fixed-slot path is the bitwise
    oracle: the paged kernel reads the same wire bytes through block-table
    indirection, and suffix-only prefill after a prefix hit lands on the
    same logits (pinned KV_SCALE32 makes pages write-order independent)."""
    cfg, seed = _family_cfg(family)
    params, _ = build_model(cfg).init(jax.random.PRNGKey(seed))
    rng = np.random.RandomState(seed)
    shared = rng.randint(0, cfg.vocab, 20).tolist()
    prompts = [shared + rng.randint(0, cfg.vocab, 5).tolist(),
               shared + rng.randint(0, cfg.vocab, 3).tolist(),
               rng.randint(0, cfg.vocab, 9).tolist(),
               shared + rng.randint(0, cfg.vocab, 7).tolist()]
    fixed = ServeEngine(cfg, params, batch_size=2, max_len=64,
                        kv_quant="mixfp4")
    paged = ServeEngine(cfg, params, batch_size=2, max_len=64,
                        kv_quant="mixfp4", kv_pool=24, kv_page_len=16)
    sf = _run_streams(fixed, prompts)
    sp = _run_streams(paged, prompts)
    assert sf == sp, (family, sf, sp)
    rep = paged.pool_report()
    assert rep["pages_active"] == 0          # clean release of every page
    assert paged.max_concurrent == 2
    if family == "dense":
        assert rep["prefix_hits"] > 0 and rep["prefix_hit_tokens"] > 0
    else:
        # prefix sharing needs row-independent prefill: the hybrid's SSM
        # state recurs over the whole prompt, and MoE's capacity router
        # couples rows (cap = f(token count)) — both pools are plain
        # allocators, so every admission prefills in full and the stream
        # equality above is exact
        assert rep["prefix_hits"] == 0


def test_paged_prefix_sharing_ragged_concurrent(small_cfg):
    """Prefix sharing under ragged CONCURRENT admissions: requests of
    different lengths sharing an off-page-boundary prefix are admitted
    into both lanes at once, so shared pages are read by one lane while
    the other decodes.  Streams must still equal the fixed-slot engine,
    and the off-boundary tail must take the eager-COW path."""
    params, _ = build_model(small_cfg).init(jax.random.PRNGKey(13))
    rng = np.random.RandomState(13)
    shared = rng.randint(0, small_cfg.vocab, 17).tolist()   # 1 page + 1 row
    prompts = [shared + rng.randint(0, small_cfg.vocab, k).tolist()
               for k in (6, 2, 9, 4)]
    fixed = ServeEngine(small_cfg, params, batch_size=2, max_len=64,
                        kv_quant="mixfp4")
    paged = ServeEngine(small_cfg, params, batch_size=2, max_len=64,
                        kv_quant="mixfp4", kv_pool=32, kv_page_len=16)
    assert _run_streams(fixed, prompts) == _run_streams(paged, prompts)
    rep = paged.pool_report()
    assert rep["prefix_hit_tokens"] > 0
    assert rep["cow_copies"] > 0      # 17-token prefix: partial-page hits
    assert rep["pages_active"] == 0


def test_paged_admission_defers_until_pages_free(small_cfg):
    """A pool too small for two concurrent requests must DEFER the second
    admission (add_request -> False, nothing consumed) instead of failing,
    then admit it once the first request's pages release."""
    params, _ = build_model(small_cfg).init(jax.random.PRNGKey(4))
    # 3 usable pages; each request needs 2 (prompt 20 + 4 new -> 23 rows)
    eng = ServeEngine(small_cfg, params, batch_size=2, max_len=32,
                      kv_quant="mixfp4", kv_pool=4, kv_page_len=16)
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, small_cfg.vocab, 20) for _ in range(2)]
    streams = _run_streams(eng, prompts, n_new=4)
    assert all(len(v) == 4 for v in streams.values()), streams
    assert eng.kv_pool.alloc_failures > 0    # second admission deferred
    assert eng.max_concurrent == 1           # never actually concurrent
    assert eng.pool_report()["pages_active"] == 0


def test_paged_composes_with_w4a4_and_buckets(small_cfg):
    """kv_pool + act_quant='mixfp4' + bucketed prefill compose: the fused
    W4A4 stream over the paged cache still matches its 2pass oracle (the
    per-row ``mixfp4-2pass-rowscale`` composition, as the fused prologue
    quantizes under per-row scales)."""
    params, _ = build_model(small_cfg).init(jax.random.PRNGKey(7))
    streams = {}
    for aq in ("mixfp4", "mixfp4-2pass-rowscale"):
        eng = ServeEngine(small_cfg, params, batch_size=1, max_len=32,
                          kv_quant="mixfp4", act_quant=aq,
                          kv_pool=8, kv_page_len=16,
                          prefill_buckets="pow2-64")
        streams[aq] = _serve_one(eng, [9, 8, 7], 5)
    assert streams["mixfp4"] == streams["mixfp4-2pass-rowscale"], streams


def test_paged_validation(small_cfg):
    params, _ = build_model(small_cfg).init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="kv_quant"):
        ServeEngine(small_cfg, params, batch_size=1, max_len=32, kv_pool=8)
    with pytest.raises(ValueError, match="multiple of 16"):
        ServeEngine(small_cfg, params, batch_size=1, max_len=32,
                    kv_quant="mixfp4", kv_pool=8, kv_page_len=8)
    with pytest.raises(ValueError, match="multiple of 16"):
        ServeEngine(small_cfg, params, batch_size=1, max_len=40,
                    kv_quant="mixfp4", kv_pool=8, kv_page_len=16)
    ssm_cfg = ArchConfig(name="pv-ssm", family="ssm", n_layers=1,
                         d_model=64, vocab=64, ssm_state=8, ssm_expand=2,
                         quant=QuantConfig(method="mixfp4"))
    ssm_params, _ = build_model(ssm_cfg).init(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="family"):
        ServeEngine(ssm_cfg, ssm_params, batch_size=1, max_len=32,
                    kv_quant="mixfp4", kv_pool=8)


def test_engine_prepads_weights_to_tuner_grid(small_cfg):
    """Satellite: packed projections are prepadded to the tile tuner's
    (k_pad, n_pad) grid at engine init, so off-grid shapes stop re-padding
    inside every jitted call.  prepad_for_tiles must be a fixed point on
    the engine's weights, and the streams above prove bitwise safety."""
    from repro.serving.engine import _prepad_group, _prepad_tree
    params, _ = build_model(small_cfg).init(jax.random.PRNGKey(0))
    eng = ServeEngine(small_cfg, params, batch_size=2, max_len=32)
    again = _prepad_tree(eng.params, _prepad_group(eng.act_quant),
                         eng.batch_size)
    for a, b in zip(jax.tree.leaves(eng.params), jax.tree.leaves(again)):
        assert a is b    # prepad is idempotent: second pass is a no-op

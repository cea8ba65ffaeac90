"""Batched serving engine over packed MixFP4 weights and (optionally) a
packed MixFP4 KV cache.

Production-shaped serving loop: requests join a continuous batch and the
projection weights are held ONLY as packed :class:`~repro.core.qtensor.QTensor`
pytrees — the paper's wire format (4-bit payloads + type-in-sign E4M3 scale
bytes = 4.5 bits/value in HBM, a ~3.55x weight-memory and bandwidth saving
over bf16 in the decode-bound regime).  Every decode step runs through
``qmm`` -> the W4A16 Pallas kernel (interpret mode on CPU, native on TPU),
decoding tiles in VMEM; no dense bf16 copy of a projection weight is
retained anywhere in the engine.

Three hot paths run over packed data end-to-end (docs/serving.md):

* ``kv_quant="mixfp4"`` carries the transformer KV cache as 1-D
  ``BlockLayout1D`` QTensors; every decode step scatters the new token's
  packed K/V bytes in place and reads the cache through the fused Pallas
  decode-attention kernel (``kernels.mixfp4_attn``) — the cache's dense
  bf16 form never exists at decode time, so the dominant decode_32k
  traffic term shrinks ~3.55x too.
* ``act_quant="mixfp4"`` (W4A4) quantizes decode AND prefill activations on
  the fly — in the W4A4 kernel's fused prologue, ONE Pallas dispatch per
  projection — using the same type-in-sign E4M3 block-scale wire encoding,
  the paper's full FP4xFP4 MMA analog (Fig. 9 decode on BOTH operands),
  for the dense, MoE, SSM and hybrid families, under PER-ROW level-2
  activation scales (+4 B/row vs Alg. 1's per-tensor reduction): each
  token row's quantized bytes are a pure function of that row, so a
  request's stream is bitwise-independent of its batchmates, of bucket
  padding, and of chunked-vs-whole prefill.
  ``"mixfp4-2pass-rowscale"`` is the explicit
  ``quantize_rows(per_row=True)`` -> W4A4-kernel two-dispatch composition
  the fused path is bitwise-identical to (the serving-level oracle and
  the degradation-ladder target); ``"mixfp4-2pass"`` is the legacy
  per-tensor two-dispatch baseline (batch-coupled, A/B only) and
  ``"mixfp4-qdq"`` its dequantize-then-W4A16 debugging oracle.
  ``act_rht=True`` additionally applies the grouped random Hadamard
  transform to activations inside the same fused prologue (signs shared
  with the pack-time weight transform, so ``D``/``H`` cancel in every
  dot product) — the serve-time outlier lever from the paper's training
  recipe.
* Admissions prefill through the models' batched ``prefill_slot`` entry:
  the whole prompt runs in ONE jit call at (P, K) prefill shapes through
  the W4A16 kernels, writing all cache rows at once, instead of the
  historical O(prompt_len) token-by-token decode replay (which also needed
  a snapshot/restore dance to keep recurrent batchmates unperturbed).
  For the transformer families, prompts additionally pad up a pow-2/64-step
  length ladder (``prefill_buckets``) so admissions stop compiling one
  prefill executable per distinct prompt length: padded suffix rows are
  causally invisible to the real positions, masked at decode until
  overwritten, and the last-position logits index the true length — the
  emitted stream is bitwise-identical to the unbucketed engine's, under
  W4A16 AND the per-row W4A4 modes (a padded suffix row quantizes under
  its own scale and cannot move a real row's bytes).
  ``prefill_compiles`` / ``prefill_cache_hits`` count the effect.

With ``mesh=`` the engine serves *sharded* packed weights
(docs/sharding.md): every projection QTensor is placed under model-axis
``NamedSharding``s derived by ``distributed.sharding.serve_packed_specs``
(column-parallel N-sharding; MoE expert stacks shard whole experts), decode
runs the W4A16 — or, with ``act_quant="mixfp4"``, the W4A4 — kernel per
shard via ``qmm_sharded``/``shard_map`` (W4A4 quantizes the replicated
activation rows ONCE and replicates the packed bytes), and the layout is
chosen so the output stream stays bitwise-identical to the single-device
packed path.  ``load_weights`` restores a packed checkpoint
straight into the sharded layout.  The KV cache is replicated for now —
its PartitionSpec story is the open ROADMAP item (docs/serving.md).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import enum
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.core import qtensor
from repro.distributed import sharding as dist_sharding
from repro.kernels.mixfp4_attn import paged_kv_blocks
from repro.models.base import ArchConfig, Ctx, build_model, pack_projections
from repro.serving.faults import InjectedFault, SystemClock
from repro.serving.journal import JournalError, RequestJournal, replay
from repro.serving.kvpool import KVPool
from repro.serving.metrics import MetricsRegistry, span, tracing
from repro.serving.scheduler import ChunkedPrefillScheduler
from repro.serving.watchdog import StepWatchdog

_TRANSFORMER_FAMILIES = ("dense", "moe", "vlm")


class RequestState(str, enum.Enum):
    """Explicit request lifecycle.  QUEUED -> PREFILLING -> RUNNING is the
    happy path; the four terminal states are mutually exclusive and each
    lands with a typed ``finish_reason`` in ``engine.counters``."""
    QUEUED = "QUEUED"
    PREFILLING = "PREFILLING"
    RUNNING = "RUNNING"
    FINISHED = "FINISHED"
    FAILED = "FAILED"
    CANCELLED = "CANCELLED"
    EXPIRED = "EXPIRED"

    def __str__(self) -> str:          # "FINISHED", not "RequestState...."
        return self.value

    @property
    def terminal(self) -> bool:
        return self in (RequestState.FINISHED, RequestState.FAILED,
                        RequestState.CANCELLED, RequestState.EXPIRED)


# Typed rejection reasons (request never entered the queue) --------------
REJECT_EMPTY_PROMPT = "empty_prompt"
REJECT_BAD_MAX_NEW = "bad_max_new_tokens"
REJECT_TOO_LONG = "too_long"
REJECT_OVER_POOL_CAPACITY = "over_pool_capacity"
REJECT_QUEUE_FULL = "queue_full"
REJECT_DRAINING = "draining"

# Typed terminal reasons -------------------------------------------------
REASON_MAX_NEW = "max_new_tokens"          # FINISHED
REASON_NAN_LOGITS = "nan_logits"           # FAILED: poisoned/overflowed row
REASON_INJECTED = "injected_fault"         # FAILED: injected fatal fault
REASON_PREFILL_ERROR = "prefill_error"     # FAILED: admission prefill raised
REASON_COW_ERROR = "cow_error"             # FAILED: COW page copy raised
REASON_POOL_ERROR = "pool_error"           # FAILED: page acquisition raised
REASON_RETRIES = "retries_exhausted"       # FAILED: transient never cleared
REASON_DEADLINE = "deadline"               # EXPIRED: total deadline passed
REASON_TTFT = "ttft_deadline"              # EXPIRED: no first token in budget
REASON_CANCELLED = "user_cancel"           # CANCELLED
REASON_SLOW_CLIENT = "slow_client"         # CANCELLED: sink queue overflow
REASON_WATCHDOG = "watchdog_timeout"       # FAILED: hung-step budget blown


class RequestValidationError(ValueError):
    """A request rejected before touching any engine state (slot, pool
    page, prefix tree).  Subclasses ValueError so historical callers'
    ``except ValueError`` handling keeps working."""

    def __init__(self, reason: str, message: str):
        super().__init__(message)
        self.reason = reason


class QueueFullError(RuntimeError):
    """Backpressure: the bounded admission queue is full.  Callers should
    shed load or retry later; the engine state is untouched."""

    reason = REJECT_QUEUE_FULL


class EngineDrainingError(RuntimeError):
    """The engine is draining (``begin_drain()``): admissions are closed
    while in-flight requests finish.  Clients should retry against a
    replacement instance; the engine state is untouched."""

    reason = REJECT_DRAINING


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray           # (len,) int32
    max_new_tokens: int = 16
    generated: list = dataclasses.field(default_factory=list)
    done: bool = False
    # First greedy token, produced by the admission prefill and emitted by
    # the first step() — None until the request has been admitted.  (It
    # used to be injected dynamically by _prefill_slot, so step() on a
    # request that skipped prefill raised AttributeError.)
    _next: int | None = None
    # lifecycle ----------------------------------------------------------
    deadline_ms: float | None = None       # total budget from submission
    ttft_budget_ms: float | None = None    # budget to the FIRST token
    state: RequestState = RequestState.QUEUED
    finish_reason: str | None = None
    error: Exception | None = dataclasses.field(default=None, repr=False)
    submitted_at: float | None = None      # engine-clock seconds
    first_token_at: float | None = None
    _last_token_at: float | None = None    # ITL anchor (metrics)
    _deferrals: int = 0                    # pool-exhaustion re-queues
    _retry_at: float = 0.0                 # backoff gate for re-admission

    def ttft_ms(self) -> float | None:
        if self.submitted_at is None or self.first_token_at is None:
            return None
        return (self.first_token_at - self.submitted_at) * 1e3


def engine_robustness_spec(*, max_queue: int = 64,
                           deadline_ms: float | None = None,
                           ttft_budget_ms: float | None = None,
                           degrade_after_deferrals: int | None = None,
                           kv_pool: int | None = None,
                           act_quant: str | None = None) -> dict:
    """Pure description of an engine's robustness configuration — the
    queue bound, deadline defaults, and which rungs of the degradation
    ladder are armed.  Used by the launch dryrun report (no engine
    build) and mirrored live by ``ServeEngine.robustness_report``."""
    ladder = []
    if act_quant == "mixfp4":
        ladder.append({"from": "fused W4A4 GEMM",
                       "to": "2-pass W4A4 (per-row scales)",
                       "trigger": "failed fused dispatch",
                       "bitwise_preserving": True})
    if kv_pool is not None:
        ladder.append({"from": "paged attention", "to": "fixed-slot",
                       "trigger": (f"admission deferred "
                                   f">= {degrade_after_deferrals} times"
                                   if degrade_after_deferrals
                                   else "disarmed (degrade_after_deferrals"
                                        "=None)"),
                       "bitwise_preserving": kv_pool is not None})
    return {
        "queue": {"max_queue": max_queue},
        "deadlines": {"deadline_ms": deadline_ms,
                      "ttft_budget_ms": ttft_budget_ms},
        "degradation_ladder": ladder,
        "states": [s.value for s in RequestState],
    }


def _prepad_group(act_quant: str) -> str:
    """Tuner path whose tile grid the engine pre-pads packed weights onto.
    Both W4A4 spellings share one tuner cache entry ('w4a4'), so the fused
    kernel and the 2-pass composition see identical storage — preserving
    their bitwise-comparability."""
    return ("w4a4" if act_quant in ("mixfp4", "mixfp4-2pass",
                                    "mixfp4-2pass-rowscale") else "w4a16")


def _prepad_tree(params, group: str, m: int):
    """Pre-pad every 2-D packed projection onto the tuner grid for ``m``
    decode rows (qtensor.prepad_for_tiles), so the per-step ``qmm``
    dispatch stops re-padding packed bytes inside every jitted call."""
    is_qt = lambda x: isinstance(x, qtensor.QTensor)
    return jax.tree.map(
        lambda l: qtensor.prepad_for_tiles(l, group, m) if is_qt(l) else l,
        params, is_leaf=is_qt)


def _packed_stats(tree) -> tuple[int, int]:
    """(wire bytes, bf16-equivalent bytes) over the QTensor leaves of a
    parameter tree — same accounting as models.base.pack_projections."""
    packed = dense = 0
    for leaf in jax.tree.leaves(
            tree, is_leaf=lambda x: isinstance(x, qtensor.QTensor)):
        if isinstance(leaf, qtensor.QTensor):
            packed += leaf.nbytes
            dense += int(np.prod(leaf.shape)) * leaf._batch_size() * 2
    return packed, dense


class ServeEngine:
    """Greedy continuous-batching decoder for the transformer families."""

    def __init__(self, cfg: ArchConfig, params, *, batch_size: int = 8,
                 max_len: int = 512, pack_weights: bool = True,
                 method: str = "mixfp4", kv_quant: str | None = None,
                 act_quant: str | None = None, act_rht: bool = False,
                 mesh=None,
                 prefill_buckets: str | None = "auto",
                 prefill_chunk: int | None = None,
                 kv_pool: int | None = None, kv_page_len: int = 16,
                 max_queue: int = 64, deadline_ms: float | None = None,
                 ttft_budget_ms: float | None = None, faults=None,
                 clock=None, degrade_after_deferrals: int | None = None,
                 retry_max: int = 3, retry_base_ms: float = 10.0,
                 retry_cap_ms: float = 1000.0,
                 journal_dir: str | None = None,
                 journal_sync: str = "batch",
                 hung_step_budget_ms: float | None = None,
                 watchdog_fail_after: int = 2):
        if max_queue < 1:
            raise ValueError(f"max_queue={max_queue} must be >= 1")
        if degrade_after_deferrals is not None and degrade_after_deferrals < 1:
            raise ValueError("degrade_after_deferrals must be None "
                             "(disarmed) or >= 1")
        if cfg.family == "encdec":
            raise ValueError(
                "ServeEngine has no source-encoding path (requests carry "
                "tokens only); an encdec model would cross-attend an "
                "all-zero memory. Drive encdec decoding through "
                "model.prefill(src_embeds)/decode_step directly.")
        if kv_quant not in (None, "bf16", "mixfp4"):
            raise ValueError(f"unknown kv_quant {kv_quant!r} "
                             "(expected None, 'bf16' or 'mixfp4')")
        has_kv = (cfg.family in _TRANSFORMER_FAMILIES
                  or (cfg.family == "hybrid" and cfg.attn_period))
        if kv_quant == "mixfp4" and not has_kv:
            raise ValueError(
                f"kv_quant='mixfp4' packs the attention KV cache; family "
                f"{cfg.family!r} has no KV cache to pack (transformers and "
                "the shared-attention hybrid do)")
        if kv_pool is not None:
            if kv_quant != "mixfp4":
                raise ValueError(
                    "kv_pool= is the paged *packed* KV path; it requires "
                    f"kv_quant='mixfp4' (got {kv_quant!r})")
            if mesh is not None:
                raise ValueError(
                    "kv_pool= with mesh= is not wired yet: the paged "
                    "attention kernel's block-table prefetch has no "
                    "shard_map spec (the fixed-slot packed cache serves "
                    "sharded engines)")
            if kv_page_len % 16 or max_len % kv_page_len:
                raise ValueError(
                    f"kv_page_len={kv_page_len} must be a multiple of 16 "
                    f"(the MixFP4 block) and divide max_len={max_len}")
        if act_quant not in (None, "bf16", "mixfp4", "mixfp4-2pass",
                             "mixfp4-2pass-rowscale", "mixfp4-qdq"):
            raise ValueError(
                f"unknown act_quant {act_quant!r} (expected None, 'bf16', "
                "'mixfp4' (fused per-row quantize+GEMM), "
                "'mixfp4-2pass-rowscale' (its two-dispatch bitwise oracle), "
                "'mixfp4-2pass' (the legacy per-tensor composition), or "
                "the 'mixfp4-qdq' debugging oracle)")
        if act_quant in ("mixfp4", "mixfp4-2pass", "mixfp4-2pass-rowscale",
                         "mixfp4-qdq") and not pack_weights:
            raise ValueError(
                "act_quant='mixfp4' is the W4A4 path — both GEMM operands "
                "on the wire format — which needs packed weights; drop "
                "pack_weights=False")
        if act_rht:
            if act_quant not in ("mixfp4", "mixfp4-2pass-rowscale"):
                raise ValueError(
                    "act_rht=True rotates activations AND packed weights "
                    "with a shared grouped Hadamard, which only the "
                    "per-row W4A4 modes consume; it requires "
                    "act_quant='mixfp4' or 'mixfp4-2pass-rowscale' "
                    f"(got {act_quant!r})")
            if not pack_weights:
                raise ValueError(
                    "act_rht=True transforms the weights at pack time "
                    "(pack_projections(act_rht=True)); drop "
                    "pack_weights=False")
        if prefill_buckets not in (None, "off", "auto", "pow2-64"):
            raise ValueError(
                f"unknown prefill_buckets {prefill_buckets!r} (expected "
                "None/'off', 'auto', or 'pow2-64')")
        if prefill_buckets == "pow2-64" \
                and cfg.family not in _TRANSFORMER_FAMILIES:
            raise ValueError(
                "prefill_buckets pads the prompt with suffix tokens, which "
                "is only sound for the transformer families (KV rows "
                "beyond the true length are masked/overwritten); the SSM "
                f"recurrent state of family {cfg.family!r} advances for "
                "every padded token")
        if prefill_chunk is not None:
            if cfg.family not in _TRANSFORMER_FAMILIES:
                raise ValueError(
                    "prefill_chunk= splits an admission's prefill into "
                    "fixed-token-budget chunks interleaved with decode, "
                    "which is only sound for the transformer families "
                    "(KV rows quantize write-order-independently and the "
                    "padded final chunk is masked); the SSM recurrent "
                    f"state of family {cfg.family!r} advances per token "
                    "and has no start_pos resume path (ROADMAP "
                    "carry-over: needs state checkpoints at chunk "
                    "boundaries)")
            if prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk={prefill_chunk} must be >= 1 token")
            if prefill_buckets == "pow2-64":
                raise ValueError(
                    "prefill_chunk= already runs every chunk at ONE "
                    "static shape (the chunk budget); it replaces the "
                    "prefill_buckets ladder — drop "
                    "prefill_buckets='pow2-64'")
        if mesh is not None and not pack_weights:
            raise ValueError(
                "mesh serving is the sharded *packed* path (QTensor "
                "payload/scales under model-axis NamedShardings); "
                "pack_weights=False has no sharded serve layout")
        self.cfg = cfg
        self.model = build_model(cfg)
        self.batch_size = batch_size
        self.max_len = max_len
        self.kv_quant = kv_quant or "bf16"
        self.act_quant = act_quant or "bf16"
        self.act_rht = act_rht
        self.mesh = mesh
        self.ctx = Ctx(jax.random.PRNGKey(0), cfg.quant, mesh=mesh,
                       act_quant=self.act_quant, act_rht=act_rht)
        if pack_weights:
            # Projection weights become packed QTensors; the dense leaves
            # are dropped from this tree (callers should release their own
            # reference if they want the full HBM saving).
            self.params, self.packed_bytes, self.dense_bytes = \
                pack_projections(params, method=method, act_rht=act_rht)
            if mesh is not None:
                # model-axis TP placement: payload/scales co-sharded at
                # block granularity, logical pspec recorded in the aux so
                # qlinear dispatches qmm_sharded; dense leaves (embed,
                # norms — the paper's exclusions) replicate
                self.weight_specs = dist_sharding.serve_packed_specs(
                    self.params, mesh)
                self.params = dist_sharding.shard_packed_tree(
                    self.params, self.weight_specs, mesh)
        else:
            self.params = params
            self.packed_bytes = self.dense_bytes = 0
        self.compression = (self.dense_bytes / self.packed_bytes
                            if self.packed_bytes else 1.0)
        if pack_weights and mesh is None:
            # pre-pad packed projections onto the decode-shape tuner grid
            # (storage only; stats above keep the logical wire bytes)
            self.params = _prepad_tree(
                self.params, _prepad_group(self.act_quant), batch_size)
        # paged KV pool (kv_pool = number of physical pages; page 0 is the
        # pool's trash page).  Prefix caching needs suffix prefill to be
        # bitwise-equal to full prefill, i.e. ROW-INDEPENDENT prefill:
        # the hybrid's SSM state recurs over the whole prompt, and MoE's
        # capacity router couples rows (cap = f(token count), so a short
        # suffix competes for different expert capacity than the full
        # prompt did).  Only the dense transformer family qualifies; the
        # others ride the pool as a plain page allocator.
        self.kv_pool_pages = kv_pool
        self.kv_page_len = kv_page_len
        if kv_pool is not None:
            self.kv_pool = KVPool(
                kv_pool, kv_page_len,
                enable_prefix=cfg.family == "dense")
            self.cache = self.model.init_cache(
                batch_size, max_len, kv_quant="mixfp4",
                pages=(kv_pool, kv_page_len))
            self.block_tables = np.zeros(
                (batch_size, max_len // kv_page_len), np.int32)
            self._slot_pages: list = [None] * batch_size
            self._copy_page = jax.jit(self._cow_copy)
            # each attention layer's window, for serve.decode's kv_blocks
            self._kv_windows = self.model.layer_windows()
        else:
            self.kv_pool = None
            if self.kv_quant == "mixfp4":
                self.cache = self.model.init_cache(batch_size, max_len,
                                                   kv_quant="mixfp4")
            else:
                self.cache = self.model.init_cache(batch_size, max_len)
        self.lengths = np.zeros((batch_size,), np.int32)
        self.slots: list[Request | None] = [None] * batch_size
        self.prefill_dispatches = 0   # jit dispatches spent on admissions
        self.admissions = 0
        self.max_concurrent = 0       # peak active slots seen by step()
        # request lifecycle: bounded admission queue, deadline defaults,
        # seeded fault injector (None in production), retry policy.  With
        # an injector installed the engine runs on ITS clock (a virtual
        # clock by default), so deadlines / TTFT / backoff are pure
        # functions of the fault schedule.
        self.max_queue = max_queue
        self.deadline_ms = deadline_ms
        self.ttft_budget_ms = ttft_budget_ms
        self.faults = faults
        if clock is not None:
            self.clock = clock
        elif faults is not None:
            self.clock = faults.clock
        else:
            self.clock = SystemClock()
        self.degrade_after_deferrals = degrade_after_deferrals
        self.retry_max = retry_max
        self.retry_base_ms = retry_base_ms
        self.retry_cap_ms = retry_cap_ms
        self.queue: collections.deque[Request] = collections.deque()
        self.requests: dict[int, Request] = {}   # uid -> every seen request
        self.counters: collections.Counter = collections.Counter()
        self._step_poison: set = set()
        # prompt-length bucketing (transformer families): pad prompts up a
        # pow-2/64-step ladder so admissions reuse one compiled prefill per
        # bucket instead of compiling per distinct length
        if prefill_buckets == "auto":
            prefill_buckets = ("pow2-64"
                               if cfg.family in _TRANSFORMER_FAMILIES
                               and prefill_chunk is None
                               else None)
        self.prefill_buckets = (None if prefill_buckets in (None, "off")
                                else prefill_buckets)
        self.prefill_compiles = 0      # distinct prefill shapes traced
        self.prefill_cache_hits = 0    # admissions that reused a shape
        self._prefill_lens: set = set()
        self._paged_suffix = (self.kv_pool is not None
                              and self.kv_pool.enable_prefix)
        # chunked-prefill scheduler (serving.scheduler): admissions enqueue
        # a PrefillJob instead of prefilling inline, and step() spends at
        # most prefill_chunk prompt tokens per step before decoding
        self.prefill_chunk = prefill_chunk
        self.scheduler = (ChunkedPrefillScheduler(prefill_chunk)
                          if prefill_chunk is not None else None)
        # observability (serving.metrics): the engine worker is the only
        # writer; readers take snapshot dicts via metrics_report()
        self.metrics = MetricsRegistry()
        self._step_prefill_tokens = 0   # prompt tokens spent this step
        self.max_prefill_tokens_per_step = 0
        # durability: append-only request journal (admission prompts,
        # emitted tokens, terminal transitions) + graceful-drain flag +
        # hung-step watchdog.  The journal writes THROUGH the existing
        # state machine (submit/_mark_terminal/step), so replaying it
        # reconstructs exactly the lifecycle the counters saw.
        self.journal_sync = journal_sync
        self.journal = (RequestJournal(journal_dir, sync=journal_sync)
                        if journal_dir is not None else None)
        self.draining = False
        self.recovered_uids: list[int] = []
        self._weights_pin: dict | None = None   # journal <-> ckpt pinning
        self.watchdog = (StepWatchdog(hung_step_budget_ms,
                                      fail_after=watchdog_fail_after)
                         if hung_step_budget_ms is not None else None)
        self._build_jits()

    def _build_jits(self):
        """(Re)build the decode/prefill jit closures for the engine's
        CURRENT ``ctx``/``_paged_suffix``.  Called at init and again by the
        degradation rungs (fused -> 2-pass rebinds ctx.act_quant; paged ->
        fixed-slot drops the block-table operand).  The inner functions'
        names name the programs in a profiler trace (``jit_serve_decode``,
        ``jit_serve_prefill``, ``jit_serve_chunk_prefill``)."""
        model = self.model

        def serve_decode(p, t, c, l):
            return model.decode_step(p, t, self.ctx, c, l)

        self._decode = jax.jit(serve_decode)
        # prefix-caching prefills take the suffix start as a dynamic
        # operand (prefix-cached admissions prefill only tokens[shared:]);
        # plain-allocator pools (hybrid/MoE) always start at 0
        paged_sfx = self._paged_suffix
        if self.prefill_buckets and paged_sfx:
            def serve_prefill(p, t, c, i, n, s0):
                return model.prefill_slot(p, t, self.ctx, c, i, true_len=n,
                                          start_pos=s0)
        elif self.prefill_buckets:
            def serve_prefill(p, t, c, i, n):
                return model.prefill_slot(p, t, self.ctx, c, i, true_len=n)
        elif paged_sfx:
            def serve_prefill(p, t, c, i, s0):
                return model.prefill_slot(p, t, self.ctx, c, i, start_pos=s0)
        else:
            # one dispatch per admission; recompiles per distinct prompt
            # length (prefill shapes)
            def serve_prefill(p, t, c, i):
                return model.prefill_slot(p, t, self.ctx, c, i)
        self._prefill = jax.jit(serve_prefill)
        # chunked prefill always rides true_len (the final partial chunk
        # pads up to the budget) + start_pos (the chunk cursor) — ONE
        # compiled prefill executable for the whole engine
        if getattr(self, "scheduler", None) is not None:
            def serve_chunk_prefill(p, t, c, i, n, s0):
                return model.prefill_slot(p, t, self.ctx, c, i, true_len=n,
                                          start_pos=s0)

            self._chunk_prefill = jax.jit(serve_chunk_prefill)

    # ------------------------------------------------------------------
    # paged-pool device helpers
    # ------------------------------------------------------------------
    def _cow_copy(self, cache, src, dst):
        """Copy page ``src``'s packed bytes into page ``dst`` in both K and
        V slabs — the eager copy-on-write step of a partial prefix hit
        (serving.kvpool).  Page axis is axis 1 of every child (behind the
        layer/app axis)."""
        def cp(qt):
            return qtensor.QTensor(
                qt.payload.at[:, dst].set(qt.payload[:, src]),
                qt.scales.at[:, dst].set(qt.scales[:, src]),
                qt.scale32, qt.method, qt.layout, qt.shape, qt.dtype)
        return dict(cache, k=cp(cache["k"]), v=cp(cache["v"]))

    def _mesh_ctx(self):
        """Ambient-mesh context for jit traces: activates the models'
        ``shard()`` constraints and the mesh-aware ``qlinear`` dispatch
        (no-op for single-device engines)."""
        return self.mesh if self.mesh is not None else contextlib.nullcontext()

    # ------------------------------------------------------------------
    # storage accounting
    # ------------------------------------------------------------------
    def kv_cache_bytes(self) -> int:
        """HBM bytes held by the KV/state cache (QTensor leaves count their
        wire bytes — 4.5 bits/value instead of bf16's 16)."""
        total = 0
        for leaf in jax.tree.leaves(
                self.cache, is_leaf=lambda x: isinstance(x, qtensor.QTensor)):
            total += int(leaf.nbytes)
        return total

    # ------------------------------------------------------------------
    # packed-weight checkpointing: the QTensor pytree round-trips through
    # CheckpointManager (payload/scales/scale32 are ordinary leaves; the
    # static layout metadata travels in the manifest spec).
    # ------------------------------------------------------------------
    def save_weights(self, directory: str, step: int = 0):
        mgr = CheckpointManager(directory)
        mgr.save_packed(step, self.params, blocking=True)
        self._pin_weights(directory, step, mgr)

    def _pin_weights(self, directory: str, step: int, mgr):
        """Record the packed-checkpoint pin (step + manifest fingerprint)
        on the engine AND in the journal, so ``recover()`` can refuse to
        resume journaled streams against different weight bytes."""
        try:
            fp = mgr.packed_fingerprint(step)
        except (OSError, ValueError, KeyError):
            fp = None
        self._weights_pin = {"dir": str(directory), "step": int(step),
                             "fingerprint": fp}
        self._journal_append({"t": "ckpt", "dir": str(directory),
                              "step": int(step), "fp": fp})
        self._journal_flush()

    def load_weights(self, directory: str, step: int | None = None):
        """Restore a packed checkpoint; a mesh engine restores each leaf
        *directly* into the sharded serve layout (per-child NamedShardings
        derived from the manifest's structural spec before any leaf bytes
        are read — no replicated intermediate tree)."""
        mgr = CheckpointManager(directory)
        if self.mesh is None:
            # checkpoint-restore I/O is the canonical transient failure
            # (flaky network filesystems): capped-backoff retries behind
            # the 'checkpoint_read' fault boundary
            restored, _ = self._with_retries(
                "checkpoint_read", lambda: mgr.restore_packed(step),
                retryable=(OSError,))
        else:
            step, spec = mgr.packed_spec(step)
            like = qtensor.tree_like(spec)
            qt_leaves = [l for l in jax.tree.leaves(
                like, is_leaf=lambda x: isinstance(x, qtensor.QTensor))
                if isinstance(l, qtensor.QTensor)]
            if all(isinstance(q.payload, jax.ShapeDtypeStruct)
                   for q in qt_leaves):
                # manifest records child shapes: derive per-child
                # NamedShardings up front and restore each leaf straight
                # onto its shards (no replicated intermediate)
                specs = dist_sharding.serve_packed_specs(like, self.mesh)
                shardings = dist_sharding.packed_restore_shardings(
                    like, specs, self.mesh)
                restored, _ = self._with_retries(
                    "checkpoint_read",
                    lambda: mgr.restore_packed(step, shardings=shardings),
                    retryable=(OSError,))
            else:
                # pre-child-shape manifest (dummy-leaf skeleton): restore
                # replicated first, then derive the layout from the
                # concrete tree and move the leaves
                restored, _ = mgr.restore_packed(step)
                specs = dist_sharding.serve_packed_specs(restored, self.mesh)
            # re-placing is a no-op move for already-placed leaves; it
            # restamps each QTensor's aux pspec to THIS engine's layout
            # (the checkpoint may have been saved under a different one)
            restored = dist_sharding.shard_packed_tree(restored, specs,
                                                       self.mesh)
            self.weight_specs = specs
        if self.act_rht and not (isinstance(restored, dict)
                                 and "rht_signs" in restored):
            raise ValueError(
                "act_rht=True engine restored a checkpoint with no "
                "'rht_signs' entry: the packed weights were not "
                "Hadamard-transformed at pack time "
                "(pack_projections(act_rht=True)), so the activation RHT "
                "would no longer cancel in the GEMM")
        self.params = restored
        # recompute storage stats from what was actually restored (a cold
        # engine built with pack_weights=False would otherwise keep 0/1.0)
        self.packed_bytes, self.dense_bytes = _packed_stats(restored)
        self.compression = (self.dense_bytes / self.packed_bytes
                            if self.packed_bytes else 1.0)
        if self.mesh is None:
            self.params = _prepad_tree(
                self.params, _prepad_group(self.act_quant), self.batch_size)
        if step is None:
            step = mgr.latest_step()
        if step is not None:
            self._pin_weights(directory, step, mgr)

    # ------------------------------------------------------------------
    # request lifecycle: validation, bounded queue, admission, faults
    # ------------------------------------------------------------------
    def _validate(self, req: Request):
        """Reject malformed requests BEFORE any engine state is touched —
        no slot, no pool page, no prefix-tree refcount.  (The over-pool-
        capacity check in particular used to be discovered only inside
        ``kv_pool.acquire``, i.e. after walking the prefix tree.)"""
        if len(req.prompt) == 0:
            self.counters[f"rejected:{REJECT_EMPTY_PROMPT}"] += 1
            raise RequestValidationError(
                REJECT_EMPTY_PROMPT,
                "empty prompt: a request must carry at least one prompt "
                "token")
        if req.max_new_tokens < 1:
            self.counters[f"rejected:{REJECT_BAD_MAX_NEW}"] += 1
            raise RequestValidationError(
                REJECT_BAD_MAX_NEW,
                "max_new_tokens must be >= 1 (the prefill itself produces "
                "the first token)")
        # the final generated token is emitted but never fed back, so the
        # highest cache position written is prompt + max_new - 2
        if len(req.prompt) + req.max_new_tokens - 1 > self.max_len:
            self.counters[f"rejected:{REJECT_TOO_LONG}"] += 1
            raise RequestValidationError(
                REJECT_TOO_LONG,
                f"request {req.uid} needs {len(req.prompt)} prompt + "
                f"{req.max_new_tokens} new tokens but the cache holds "
                f"max_len={self.max_len}")
        if self.kv_pool is not None:
            need = self.kv_pool.pages_needed(len(req.prompt),
                                             req.max_new_tokens)
            if need > self.kv_pool.pages_total:
                self.counters[f"rejected:{REJECT_OVER_POOL_CAPACITY}"] += 1
                raise RequestValidationError(
                    REJECT_OVER_POOL_CAPACITY,
                    f"request {req.uid} needs {need} pool pages but the "
                    f"pool holds {self.kv_pool.pages_total} (deferring it "
                    "would livelock: no amount of draining frees enough)")

    def submit(self, req: Request):
        """Enqueue a request on the bounded admission queue (strict FIFO).
        Raises :class:`RequestValidationError` / :class:`QueueFullError` /
        :class:`EngineDrainingError` with a typed reason; on success the
        request is QUEUED and will be admitted by a later ``step()`` as
        slots and pool pages free up."""
        self._validate(req)
        if self.draining:
            self.counters[f"rejected:{REJECT_DRAINING}"] += 1
            raise EngineDrainingError(
                "engine is draining: admissions are closed while "
                "in-flight requests finish (retry against a replacement "
                "instance)")
        if len(self.queue) >= self.max_queue:
            self.counters[f"rejected:{REJECT_QUEUE_FULL}"] += 1
            raise QueueFullError(
                f"admission queue is full ({self.max_queue} requests); "
                "shed load or retry after a drain")
        req.state = RequestState.QUEUED
        req.submitted_at = self.clock()
        self.requests[req.uid] = req
        self.queue.append(req)
        self.counters["submitted"] += 1
        self._journal_submit(req)

    def cancel(self, uid: int, reason: str = REASON_CANCELLED) -> bool:
        """Cancel a queued or in-flight request.  Returns True if the
        request transitioned to CANCELLED (slot and pool pages released);
        False if it is unknown or already terminal.  ``reason`` types the
        terminal verdict (``user_cancel`` by default; the HTTP front-end
        passes ``slow_client`` for sink-overflow evictions)."""
        req = self.requests.get(uid)
        if req is None or req.state.terminal:
            return False
        if req.state is RequestState.QUEUED:
            with contextlib.suppress(ValueError):
                self.queue.remove(req)
            self._mark_terminal(req, RequestState.CANCELLED, reason)
            return True
        i = next(i for i, s in enumerate(self.slots) if s is req)
        self._finish_request(i, RequestState.CANCELLED, reason)
        return True

    def has_work(self) -> bool:
        return bool(self.queue) or any(
            s is not None and not s.done for s in self.slots)

    # -- journal write-through -----------------------------------------
    def _journal_append(self, rec: dict):
        """Append one record behind the ``journal_write`` fault boundary.
        Transients (and real OSErrors) retry with capped backoff; a fatal
        failure DISABLES journaling and keeps serving (fail-open: losing
        durability is a counter + alert, not an outage) — recovery then
        resumes from the last committed record, which greedy determinism
        makes safe (the re-decoded tokens are bitwise the lost ones)."""
        if self.journal is None:
            return
        try:
            self._with_retries("journal_write",
                               lambda: self.journal.append(rec),
                               retryable=(OSError,))
        except (InjectedFault, OSError) as e:
            self.counters["journal_write_failed"] += 1
            self.counters["journal_disabled"] = 1
            with contextlib.suppress(Exception):
                self.journal.close()
            self.journal = None
            del e

    def _journal_submit(self, req: Request):
        if self.journal is None:
            return
        rec = {"t": "submit", "uid": req.uid,
               "prompt": [int(t) for t in np.asarray(req.prompt).ravel()],
               "max_new_tokens": int(req.max_new_tokens)}
        if req.deadline_ms is not None:
            rec["deadline_ms"] = req.deadline_ms
        if req.ttft_budget_ms is not None:
            rec["ttft_budget_ms"] = req.ttft_budget_ms
        self._journal_append(rec)

    def _journal_flush(self):
        """Step-boundary flush: under ``journal_sync='batch'`` this is the
        one fsync that commits the whole step's token records."""
        if self.journal is not None:
            try:
                self.journal.flush()
            except OSError:
                self.counters["journal_write_failed"] += 1
                self.counters["journal_disabled"] = 1
                with contextlib.suppress(Exception):
                    self.journal.close()
                self.journal = None

    # -- fault hooks / clock -------------------------------------------
    def _fire(self, site: str, *, uid: int | None = None, scoped=True):
        """Cross one injector boundary.  ``scoped`` sites victimize the
        request passed as ``uid``; the decode site victimizes among all
        active requests.  Returns the FaultAction (or None)."""
        if self.faults is None:
            return None
        active = () if scoped else tuple(
            r.uid for r in self.slots if r is not None and not r.done)
        act = self.faults.fire(site, uid=uid, active_uids=active)
        if act.delay_ms:
            self.counters["injected_slow_ms"] += int(act.delay_ms)
        return act

    def _sleep(self, seconds: float):
        """Backoff sleep on the engine clock: a virtual clock advances
        deterministically, the system clock really sleeps (capped)."""
        self.clock.sleep(seconds)

    def _backoff_s(self, attempt: int) -> float:
        """Capped exponential backoff: base * 2^(attempt-1), in seconds."""
        return min(self.retry_base_ms * 2.0 ** max(attempt - 1, 0),
                   self.retry_cap_ms) / 1e3

    def _with_retries(self, site: str, fn, *, uid=None, retryable=()):
        """Run ``fn`` behind the ``site`` fault boundary with capped
        exponential backoff on transient failures (injected transients and
        any real exception type in ``retryable``, e.g. OSError for
        checkpoint reads).  Non-transient faults propagate immediately;
        exhausting the budget re-raises the last failure."""
        attempt = 0
        while True:
            try:
                act = self._fire(site, uid=uid)
                if act is not None and act.error is not None:
                    raise act.error
                return fn() if fn is not None else act
            except InjectedFault as e:
                if not e.transient:
                    raise
                last = e
            except retryable as e:
                last = e
            attempt += 1
            if attempt > self.retry_max:
                self.counters[f"retries_exhausted:{site}"] += 1
                raise last
            self.counters[f"retries:{site}"] += 1
            self._sleep(self._backoff_s(attempt))

    # -- admission ------------------------------------------------------
    def add_request(self, req: Request) -> bool:
        """Direct (queue-bypassing) admission — the historical API.
        Returns True when the request was CONSUMED (admitted, or failed
        terminally by an injected admission fault), False when the caller
        should retry later (no free slot / pool exhausted)."""
        self._validate(req)
        if req.submitted_at is None:
            req.submitted_at = self.clock()
        if req.uid not in self.requests:
            self._journal_submit(req)    # once per uid across re-tries
        self.requests[req.uid] = req
        res = self._try_admit(req)
        if res == "deferred":
            req._deferrals += 1
            self.counters["deferred_admissions"] += 1
        return res in ("admitted", "failed")

    def _try_admit(self, req: Request) -> str:
        """Try to place ``req`` in a free slot: 'admitted', 'no_slot',
        'deferred' (pool exhausted — retryable), or 'failed' (a fatal
        admission fault consumed the request; its slot and pages were
        rolled back and it is terminally FAILED)."""
        free = next((i for i, s in enumerate(self.slots) if s is None), None)
        if free is None:
            return "no_slot"
        i = free
        if req.generated:
            # a recovered request resumes mid-stream: re-prefill its full
            # token history instead of just the prompt
            return self._resume_admit(i, req)
        if self.kv_pool is None:
            self.slots[i] = req
            req.state = RequestState.PREFILLING
            # a reused slot starts over at position 0 with zeroed cache
            # rows — no KV / SSM state leaks from the previous occupant
            self.lengths[i] = 0
            self.cache = self.model.reset_slot(self.cache, i)
            if self.scheduler is not None:
                # chunked admission: the slot is held but no prefill runs
                # here — step() drains the job one chunk at a time.  While
                # PREFILLING, lengths[i] tracks the chunk cursor so the
                # batched decode's junk scatter for this lane always lands
                # at the NEXT chunk's start row, where it is masked until
                # overwritten by that chunk's real write.
                self.scheduler.enqueue(req.uid, i, req, len(req.prompt))
                return "admitted"
            if not self._guarded_prefill(i, req):
                return "failed"
            req.state = RequestState.RUNNING
            return "admitted"
        # paged path: admit by PAGE availability too — map cached prefix
        # pages, allocate the rest (evicting LRU cached pages as needed).
        # A pool that cannot cover the request defers it.
        act = self._fire("pool_acquire", uid=req.uid)
        if act is not None and act.error is not None:
            if act.error.transient:
                return "deferred"      # backs off like real exhaustion
            self._mark_terminal(req, RequestState.FAILED, REASON_POOL_ERROR,
                                error=act.error)
            return "failed"
        denied = act is not None and act.deny
        adm = None if denied else self.kv_pool.acquire(req.prompt,
                                                       req.max_new_tokens)
        if denied:
            self.counters["injected_pool_denials"] += 1
        if adm is None:
            return "deferred"
        self.slots[i] = req
        req.state = RequestState.PREFILLING
        self.lengths[i] = 0
        self.cache = self.model.reset_slot(self.cache, i)
        self._slot_pages[i] = adm.pages
        row = np.zeros((self.block_tables.shape[1],), np.int32)
        row[:len(adm.pages)] = adm.pages
        self.block_tables[i] = row
        self.cache = dict(self.cache,
                          pages=jnp.asarray(self.block_tables))
        if adm.cow is not None:
            cow_act = self._fire("cow_copy", uid=req.uid)
            if cow_act is not None and cow_act.error is not None:
                # pool-page failure mid-COW: quarantine via the same
                # rollback as any admission fault — _finish_slot releases
                # the acquired pages (kvpool.release unwinds refcounts for
                # pages never registered in the tree too)
                self._finish_request(i, RequestState.FAILED,
                                     REASON_COW_ERROR, error=cow_act.error)
                return "failed"
            src, dst = adm.cow
            self.cache = self._copy_page(self.cache, jnp.int32(src),
                                         jnp.int32(dst))
        if self.scheduler is not None:
            # chunked admission (pages mapped, prefix COW done): prefill
            # starts at the cached-prefix cursor; kv_pool.insert is
            # DEFERRED to job completion — no page may be registered for
            # prefix hits until its bytes are final.
            self.scheduler.enqueue(req.uid, i, req, len(req.prompt),
                                   start_pos=adm.shared_len)
            self.lengths[i] = adm.shared_len
            return "admitted"
        if not self._guarded_prefill(i, req, start_pos=adm.shared_len):
            return "failed"
        # register the prompt's pages for future prefix hits (their
        # bytes are final now: eager COW means no shared page is ever
        # written after this point)
        self.kv_pool.insert(req.prompt, adm.pages)
        req.state = RequestState.RUNNING
        return "admitted"

    def _resume_admit(self, i: int, req: Request) -> str:
        """Admit a request that already holds generated tokens (recovery
        after a restart): re-prefill its full history
        ``prompt ++ generated[:-1]`` into slot ``i`` — the same replay
        the paged->fixed-slot degradation rung uses, value-preserving
        under greedy decode and *bitwise* under W4A16 and the per-row
        W4A4 modes (the pinned ``KV_SCALE32`` write-order contract makes
        every cache row a pure function of the token history).  Decode
        then continues by feeding ``generated[-1]`` at the history
        length, exactly where the pre-crash engine stopped.

        The history runs in ONE prefill dispatch even on chunked-prefill
        engines (chunked prefill is bitwise whole-prefill, so skipping
        the chunk ledger changes cost, not bytes).  On paged engines the
        pages stay anonymous (not prefix-registered): the trailing page
        is still being written by decode, and a restarted pool has no
        sharers to serve anyway."""
        hist_tail = np.asarray(req.generated[:-1], np.int32)
        history = np.asarray(req.prompt, np.int32)
        if hist_tail.size:
            history = np.concatenate([history, hist_tail])
        # same final cache footprint as the original request:
        # len(history) + shim_new - 1 == len(prompt) + max_new - 1
        shim_new = req.max_new_tokens - max(len(req.generated) - 1, 0)
        shim = Request(uid=req.uid, prompt=history,
                       max_new_tokens=shim_new)
        if self.kv_pool is not None:
            act = self._fire("pool_acquire", uid=req.uid)
            if act is not None and act.error is not None:
                if act.error.transient:
                    return "deferred"
                self._mark_terminal(req, RequestState.FAILED,
                                    REASON_POOL_ERROR, error=act.error)
                return "failed"
            if act is not None and act.deny:
                self.counters["injected_pool_denials"] += 1
                return "deferred"
            adm = self.kv_pool.acquire(history, shim_new)
            if adm is None:
                return "deferred"
            self.slots[i] = req
            req.state = RequestState.PREFILLING
            self.lengths[i] = 0
            self.cache = self.model.reset_slot(self.cache, i)
            self._slot_pages[i] = adm.pages
            row = np.zeros((self.block_tables.shape[1],), np.int32)
            row[:len(adm.pages)] = adm.pages
            self.block_tables[i] = row
            self.cache = dict(self.cache,
                              pages=jnp.asarray(self.block_tables))
            if adm.cow is not None:
                cow_act = self._fire("cow_copy", uid=req.uid)
                if cow_act is not None and cow_act.error is not None:
                    self._finish_request(i, RequestState.FAILED,
                                         REASON_COW_ERROR,
                                         error=cow_act.error)
                    return "failed"
                src, dst = adm.cow
                self.cache = self._copy_page(self.cache, jnp.int32(src),
                                             jnp.int32(dst))
            start_pos = adm.shared_len
        else:
            self.slots[i] = req
            req.state = RequestState.PREFILLING
            self.lengths[i] = 0
            self.cache = self.model.reset_slot(self.cache, i)
            start_pos = 0
        try:
            self._with_retries("prefill", None, uid=req.uid)
            self._prefill_slot(i, shim, start_pos=start_pos)
        except InjectedFault as e:
            reason = REASON_RETRIES if e.transient else REASON_INJECTED
            self._finish_request(i, RequestState.FAILED, reason, error=e)
            return "failed"
        except Exception as e:
            self._finish_request(i, RequestState.FAILED,
                                 REASON_PREFILL_ERROR, error=e)
            raise
        # lengths[i] = len(history) (set by _prefill_slot); the resumed
        # decode feeds generated[-1] there next step, exactly where the
        # pre-crash engine stopped.  (A request with NO emitted tokens
        # never lands here — it re-admits through the ordinary
        # prompt-prefill path, which stages the first token itself.)
        req.state = RequestState.RUNNING
        self.counters["resumed"] += 1
        return "admitted"

    def _guarded_prefill(self, i: int, req: Request, start_pos: int = 0):
        """Admission prefill behind the 'prefill' fault boundary.  On a
        fatal fault the slot is quarantined (pages released, prefix-tree
        refcounts unwound, block-table row pointed at the trash page) and
        the request lands FAILED with a typed reason; a REAL prefill
        exception additionally propagates after the same rollback, so the
        engine never holds a half-admitted slot."""
        try:
            self._with_retries("prefill", None, uid=req.uid)
            self._prefill_slot(i, req, start_pos=start_pos)
            return True
        except InjectedFault as e:
            reason = REASON_RETRIES if e.transient else REASON_INJECTED
            self._finish_request(i, RequestState.FAILED, reason, error=e)
            return False
        except Exception as e:
            self._finish_request(i, RequestState.FAILED,
                                 REASON_PREFILL_ERROR, error=e)
            raise

    # -- queue pump / deadlines ----------------------------------------
    def _pump(self):
        """Admit from the bounded queue in strict FIFO order.  A deferred
        head (pool exhausted) backs off exponentially; while it backs off
        nothing behind it is admitted (FIFO fairness).  An IDLE engine
        sleeps the clock up to the head's retry gate instead of spinning —
        with a virtual clock this is what makes deferred admissions
        livelock-free."""
        while self.queue:
            head = self.queue[0]
            if head.state is not RequestState.QUEUED:
                self.queue.popleft()       # cancelled/expired while queued
                continue
            now = self.clock()
            if head._retry_at > now:
                if any(s is not None for s in self.slots):
                    return                 # let the batch drain first
                self._sleep(head._retry_at - now)
                continue
            res = self._try_admit(head)
            if res in ("admitted", "failed"):
                self.queue.popleft()
                continue
            if res == "no_slot":
                return
            # deferred: pool exhausted past what a drain may free
            head._deferrals += 1
            self.counters["deferred_admissions"] += 1
            if (self.degrade_after_deferrals is not None
                    and head._deferrals >= self.degrade_after_deferrals
                    and self.kv_pool is not None):
                self._degrade_to_fixed_slot()
                continue                   # re-admit on the fixed path
            head._retry_at = self.clock() + self._backoff_s(head._deferrals)
            return

    def _deadline_for(self, req: Request) -> float | None:
        return req.deadline_ms if req.deadline_ms is not None \
            else self.deadline_ms

    def _ttft_for(self, req: Request) -> float | None:
        return req.ttft_budget_ms if req.ttft_budget_ms is not None \
            else self.ttft_budget_ms

    def _expire_deadlines(self):
        """Expire queued and in-flight requests past their total deadline,
        and first-token-less requests past their TTFT budget."""
        now = self.clock()

        def over(req, budget_ms):
            return (budget_ms is not None and req.submitted_at is not None
                    and (now - req.submitted_at) * 1e3 > budget_ms)

        for req in [r for r in self.queue
                    if r.state is RequestState.QUEUED]:
            if over(req, self._deadline_for(req)) \
                    or over(req, self._ttft_for(req)):
                reason = (REASON_DEADLINE
                          if over(req, self._deadline_for(req))
                          else REASON_TTFT)
                with contextlib.suppress(ValueError):
                    self.queue.remove(req)
                self._mark_terminal(req, RequestState.EXPIRED, reason)
        for i, req in enumerate(self.slots):
            if req is None or req.done:
                continue
            if over(req, self._deadline_for(req)):
                self._finish_request(i, RequestState.EXPIRED,
                                     REASON_DEADLINE)
            elif req.first_token_at is None and over(req, self._ttft_for(req)):
                self._finish_request(i, RequestState.EXPIRED, REASON_TTFT)

    # -- graceful degradation ------------------------------------------
    def _degrade_fused(self, err=None):
        """Fused W4A4 dispatch failed: fall back to the explicit
        quantize_rows(per_row=True) -> W4A4-kernel two-dispatch
        composition ('mixfp4-2pass-rowscale').  The fused path is
        bitwise-identical to it by construction (PR 5/9, shared 'w4a4'
        tuner group + prepadded storage + the same per-row scale
        derivation), so the stream is preserved exactly — only dispatch
        count and latency change.  ``act_rht`` carries over: the 2-pass
        composition applies the same grouped Hadamard before quantizing."""
        if self.act_quant != "mixfp4":
            raise RuntimeError(
                "fused-dispatch degradation requested but the engine is "
                f"not on the fused W4A4 path (act_quant={self.act_quant!r})"
            ) from err
        self.act_quant = "mixfp4-2pass-rowscale"
        self.ctx = Ctx(jax.random.PRNGKey(0), self.cfg.quant, mesh=self.mesh,
                       act_quant=self.act_quant, act_rht=self.act_rht)
        self._prefill_lens.clear()
        self._build_jits()
        self.counters["degraded_fused_to_2pass"] += 1

    def _degrade_to_fixed_slot(self):
        """Pool exhaustion past the deferral budget: abandon the paged
        pool for the fixed-slot packed KV cache.  Every in-flight request
        is migrated by re-prefilling its full token history
        (prompt ++ generated[:-1]) into the fresh cache — greedy decode
        makes that replay value-preserving (bitwise for the dense family,
        the one with prefix sharing enabled; PR 2/6 replay-bitwise
        property), and the invariant lengths = p_len + len(generated) - 1
        is exactly the history length."""
        live = [(i, r) for i, r in enumerate(self.slots) if r is not None]
        self.kv_pool = None
        self.kv_pool_pages = None
        self._paged_suffix = False
        self.cache = self.model.init_cache(self.batch_size, self.max_len,
                                           kv_quant="mixfp4")
        self._prefill_lens.clear()
        self._build_jits()
        self.counters["degraded_paged_to_fixed"] += 1
        for i, req in live:
            if (self.scheduler is not None
                    and req.state is RequestState.PREFILLING):
                # a mid-prefill chunk job restarts from position 0 on the
                # fresh fixed-slot cache (its cached-prefix rows lived in
                # the abandoned pool pages)
                self.cache = self.model.reset_slot(self.cache, i)
                self.lengths[i] = 0
                self.scheduler.restart(req.uid, 0)
                continue
            history = np.asarray(req.prompt, np.int32)
            if req.generated:
                history = np.concatenate(
                    [history, np.asarray(req.generated[:-1], np.int32)])
            self.cache = self.model.reset_slot(self.cache, i)
            shim = Request(uid=req.uid, prompt=history,
                           max_new_tokens=req.max_new_tokens)
            self._prefill_slot(i, shim)     # lengths[i] = len(history)
            if not req.generated:
                req._next = shim._next      # first token not emitted yet

    def robustness_report(self) -> dict:
        """Live robustness state: queue depth/bounds, deadline config,
        degradation ladder position, lifecycle counters, and terminal
        state totals.  The static shape mirrors
        :func:`engine_robustness_spec`."""
        spec = engine_robustness_spec(
            max_queue=self.max_queue, deadline_ms=self.deadline_ms,
            ttft_budget_ms=self.ttft_budget_ms,
            degrade_after_deferrals=self.degrade_after_deferrals,
            kv_pool=self.kv_pool_pages, act_quant=self.act_quant)
        states = collections.Counter(
            str(r.state) for r in self.requests.values())
        spec["queue"]["depth"] = len(self.queue)
        spec["counters"] = dict(self.counters)
        spec["request_states"] = dict(states)
        spec["act_quant"] = self.act_quant
        spec["paged"] = self.kv_pool is not None
        spec["draining"] = self.draining
        spec["journaled"] = self.journal is not None
        if self.watchdog is not None:
            spec["watchdog"] = self.watchdog.report()
        return spec

    # -- graceful drain / crash recovery -------------------------------
    def begin_drain(self):
        """Close admissions: ``submit()`` now rejects with the typed
        ``draining`` reason while in-flight (and already-queued) requests
        keep stepping to completion.  Idempotent."""
        if not self.draining:
            self.draining = True
            self.counters["drain_begun"] = 1

    def finish_drain(self) -> dict:
        """Snapshot the ledger after the drain loop stops: one ``ledger``
        journal record (counters, per-request final states, any
        mid-prefill cursors) committed with a forced fsync — whatever the
        steady-state ``journal_sync`` policy, the drain snapshot itself
        is durable.  Requests still live at the drain deadline stay
        non-terminal in the journal: the NEXT process recovers them."""
        survivors = [uid for uid, r in self.requests.items()
                     if not r.state.terminal]
        if self.journal is not None:
            rec = {"t": "ledger",
                   "counters": {k: float(v)
                                for k, v in self.counters.items()},
                   "requests": {str(uid): {"state": str(r.state),
                                           "reason": r.finish_reason,
                                           "n_tokens": len(r.generated)}
                                for uid, r in self.requests.items()},
                   "survivors": survivors}
            if self.scheduler is not None:
                rec["prefill_jobs"] = self.scheduler.jobs_report()
            self._journal_append(rec)
            if self.journal is not None:
                try:
                    self.journal.flush(force_sync=True)
                except OSError:
                    self.counters["journal_write_failed"] += 1
        terminal = len(self.requests) - len(survivors)
        return {"drained": not survivors, "completed": terminal,
                "survivors": survivors}

    def drain(self, deadline_ms: float | None = None,
              max_steps: int = 10000) -> dict:
        """Blocking graceful drain: close admissions, step until the
        batch and queue empty or ``deadline_ms`` passes (on the engine
        clock), then snapshot the ledger.  Returns the
        :meth:`finish_drain` report plus the steps spent.  The HTTP
        worker drives the same three phases non-blockingly
        (serving.server)."""
        self.begin_drain()
        t0 = self.clock()
        steps = 0
        while self.has_work() and steps < max_steps:
            if deadline_ms is not None \
                    and (self.clock() - t0) * 1e3 > deadline_ms:
                break
            self.step()
            steps += 1
        report = self.finish_drain()
        report["steps"] = steps
        return report

    def recover(self, journal_dir: str | None = None) -> dict:
        """Rebuild every non-terminal journaled request into THIS (fresh)
        engine and continue decode.

        Each live request is reconstructed with its journaled prompt and
        token history and re-enters the batch through the resume
        admission path (:meth:`_resume_admit`): the full history
        ``prompt ++ generated[:-1]`` re-prefills into a fresh slot/pool
        pages — bitwise the pre-crash cache rows under the pinned
        ``KV_SCALE32`` contract — and decode resumes by feeding
        ``generated[-1]`` exactly where the dead process stopped.  Under
        greedy decode the resumed stream is bitwise-identical to the
        uninterrupted run (W4A16 and the per-row W4A4 modes;
        tests/test_recovery.py property-tests fixed-slot, paged and
        chunked-prefill engines), and tokens that were emitted but lost
        to an unsynced journal tail are simply re-derived and re-emitted.

        A journal that pins packed weights (``ckpt`` record) refuses to
        resume unless this engine restored the same step with the same
        manifest fingerprint — bitwise resume is only promised under the
        same weight bytes.  Requests whose token count already reached
        ``max_new_tokens`` (terminal record lost in the tail) are
        finalized FINISHED without re-admission."""
        if journal_dir is not None:
            if self.journal is None:
                self.journal = RequestJournal(journal_dir,
                                              sync=self.journal_sync)
            elif os.path.abspath(self.journal.dir) \
                    != os.path.abspath(journal_dir):
                raise JournalError(
                    f"engine already journals to {self.journal.dir}; "
                    f"refusing to recover from {journal_dir}")
        if self.journal is None:
            raise JournalError(
                "recover() needs a journal: pass journal_dir= or "
                "construct the engine with journal_dir=")
        state = replay(self.journal.records)
        ck = state.checkpoint
        if ck is not None:
            pin = self._weights_pin
            if pin is None:
                raise JournalError(
                    f"journal pins packed weights to step {ck['step']} "
                    f"of {ck['dir']} but this engine never restored a "
                    "checkpoint; load_weights() that step first — "
                    "bitwise resume is only promised under the same "
                    "weight bytes")
            if ck.get("fingerprint") and pin.get("fingerprint") \
                    and ck["fingerprint"] != pin["fingerprint"]:
                raise JournalError(
                    f"journal pins packed weights to manifest "
                    f"fingerprint {ck['fingerprint']} (step "
                    f"{ck['step']}) but this engine restored "
                    f"{pin['fingerprint']} (step {pin['step']})")
            if ck.get("step") != pin.get("step"):
                raise JournalError(
                    f"journal pins packed weights to step {ck['step']} "
                    f"but this engine restored step {pin['step']}")
        report = {"replayed_records": len(self.journal.records),
                  "requests": len(state.requests),
                  "already_terminal": 0, "resumed": 0, "finalized": 0,
                  "dangling_tokens": state.dangling_tokens,
                  "truncated_bytes":
                      self.journal.stats.get("truncated_bytes", 0),
                  "corrupt_record_index":
                      self.journal.stats.get("corrupt_record_index")}
        now = self.clock()
        for rr in state.requests.values():
            if rr.terminal:
                report["already_terminal"] += 1
                continue
            req = Request(uid=rr.uid,
                          prompt=np.asarray(rr.prompt, np.int32),
                          max_new_tokens=rr.max_new_tokens,
                          generated=list(rr.tokens),
                          deadline_ms=rr.deadline_ms,
                          ttft_budget_ms=rr.ttft_budget_ms)
            # deadline anchors restart at recovery: the dead process's
            # wall time is gone and a recovered stream should not expire
            # the instant it resumes
            req.submitted_at = now
            if rr.tokens:
                req.first_token_at = now
                req._last_token_at = now
            self.requests[req.uid] = req
            self.recovered_uids.append(req.uid)
            if len(rr.tokens) >= rr.max_new_tokens:
                self._mark_terminal(req, RequestState.FINISHED,
                                    REASON_MAX_NEW)
                report["finalized"] += 1
                continue
            req.state = RequestState.QUEUED
            self.queue.append(req)
            report["resumed"] += 1
            self.counters["recovered"] += 1
        # place as many as fit now; the rest re-admit as slots free up
        # (recovery may requeue past max_queue — repopulation, not load)
        self._pump()
        self._journal_flush()
        return report

    # -- terminal transitions ------------------------------------------
    def _mark_terminal(self, req: Request, state: RequestState, reason: str,
                       error: Exception | None = None):
        req.state = state
        req.finish_reason = reason
        req.error = error
        req.done = True
        self.counters[f"{state.value.lower()}:{reason}"] += 1
        self._journal_append({"t": "terminal", "uid": req.uid,
                              "state": state.value, "reason": reason})

    def _finish_request(self, i: int, state: RequestState, reason: str,
                        error: Exception | None = None):
        """Terminal transition for the request in slot ``i`` + slot
        quarantine/rollback: pool pages released (prefix-tree refcounts
        unwound for registered pages, free-listed for anonymous ones) and
        the block-table row pointed at the trash page."""
        req = self.slots[i]
        self._mark_terminal(req, state, reason, error=error)
        if self.scheduler is not None:
            self.scheduler.drop(req.uid)   # forget any mid-prefill cursor
        self._finish_slot(i)

    @staticmethod
    def bucket_len(p_len: int, max_len: int) -> int:
        """The pow-2/64-step prompt-length ladder: next power of two below
        64, then 64-step rungs, clamped to the cache length."""
        b = 8
        while b < min(p_len, 64):
            b *= 2
        if p_len > 64:
            b = -(-p_len // 64) * 64
        return min(b, max_len)

    def _prefill_slot(self, i: int, req: Request, start_pos: int = 0):
        """Single-slot batched prefill: ONE jit dispatch runs the whole
        prompt through ``model.prefill_slot`` at (1, P) shapes, writing all
        of slot ``i``'s cache rows at once.  Other slots' batch rows are
        never touched (the model slices/scatters only row ``i``), so an
        admission is invisible to its batchmates for all families with no
        snapshot/restore.

        With ``prefill_buckets`` active the prompt pads up the length
        ladder (suffix zeros) and the true length rides along as a dynamic
        operand, so nearby prompt lengths share one compiled prefill; the
        emitted token and the real cache rows are bitwise those of the
        exact-length call.

        ``start_pos > 0`` (paged transformers only) is a prefix-cache hit:
        the first ``start_pos`` prompt tokens are already served by mapped
        pool pages, so only the prompt *suffix* runs — the admission's
        prefill cost shrinks by the shared prefix."""
        p_len = len(req.prompt)
        toks = np.asarray(req.prompt, np.int32)[start_pos:]
        s_len = len(toks)  # >= 1: the pool's match stops at p_len - 1
        if self.prefill_buckets:
            pb = self.bucket_len(s_len, self.max_len - start_pos)
            if pb > s_len:
                toks = np.pad(toks, (0, pb - s_len))
        shape_key = len(toks)
        if shape_key in self._prefill_lens:
            self.prefill_cache_hits += 1
        else:
            self._prefill_lens.add(shape_key)
            self.prefill_compiles += 1
        tokens = jnp.asarray(toks[None, :])
        with self._mesh_ctx():
            if self.prefill_buckets and self._paged_suffix:
                logits, self.cache = self._prefill(
                    self.params, tokens, self.cache, jnp.int32(i),
                    jnp.int32(s_len), jnp.int32(start_pos))
            elif self.prefill_buckets:
                logits, self.cache = self._prefill(
                    self.params, tokens, self.cache, jnp.int32(i),
                    jnp.int32(s_len))
            elif self._paged_suffix:
                logits, self.cache = self._prefill(
                    self.params, tokens, self.cache, jnp.int32(i),
                    jnp.int32(start_pos))
            else:
                logits, self.cache = self._prefill(
                    self.params, tokens, self.cache, jnp.int32(i))
        self.lengths[i] = p_len
        with span("sync.first_token"):
            req._next = int(jnp.argmax(logits[0]))
        self.prefill_dispatches += 1
        self.admissions += 1
        # per-step prefill-token ledger: without the chunk scheduler a
        # whole prompt lands in one step — this is exactly the decode
        # stall the frontend benchmark quantifies
        self._step_prefill_tokens += s_len

    def _finish_slot(self, i: int):
        """Free slot ``i``.  A paged engine also releases the request's
        pages back to the pool (tree-registered pages park in the LRU,
        still servable as prefix hits) and points the slot's block-table
        row at the trash page — the inactive lane's decode scatters must
        never land in pages the pool may re-grant."""
        self.slots[i] = None
        if self.kv_pool is not None:
            pages = self._slot_pages[i]
            if pages:
                self.kv_pool.release(pages)
            self._slot_pages[i] = None
            self.block_tables[i] = 0
            self.lengths[i] = 0
            self.cache = dict(
                self.cache, pages=self.cache["pages"].at[i].set(0))

    def pool_report(self) -> dict | None:
        """Pool occupancy / prefix-hit / eviction counters (None when the
        engine is not paged)."""
        return None if self.kv_pool is None else self.kv_pool.stats()

    # -- chunked prefill (serving.scheduler) ---------------------------
    def _sched_run_chunk(self):
        """Spend this step's chunk budget on the FIFO-head prefill job:
        ONE jit dispatch runs ``chunk`` prompt tokens from the job cursor
        (the final partial chunk pads up to the budget and rides
        ``true_len`` masking, so every chunk shares one compiled
        executable).  Runs behind the 'prefill' fault boundary with the
        same quarantine/rollback as the whole-prompt path.  On job
        completion the request flips RUNNING with its first token staged
        in ``_next`` — the emit loop right after this call emits it, so a
        chunked admission's stream is positioned exactly like an
        unchunked one's."""
        job = self.scheduler.head()
        if job is None:
            return
        req, i = job.req, job.slot
        start = job.cursor
        n_real = min(self.scheduler.chunk, job.p_len - start)
        with span("chunk_prefill", start=start, tokens=n_real):
            # never let start + chunk cross max_len: dynamic_update_slice
            # CLAMPS out-of-range starts, which would silently shift rows
            pad_to = min(self.scheduler.chunk, self.max_len - start)
            toks = np.asarray(req.prompt, np.int32)[start:start + n_real]
            if pad_to > n_real:
                toks = np.pad(toks, (0, pad_to - n_real))
            if len(toks) in self._prefill_lens:
                self.prefill_cache_hits += 1
            else:
                self._prefill_lens.add(len(toks))
                self.prefill_compiles += 1
            try:
                self._with_retries("prefill", None, uid=req.uid)
            except InjectedFault as e:
                reason = REASON_RETRIES if e.transient else REASON_INJECTED
                self._finish_request(i, RequestState.FAILED, reason, error=e)
                return
            tokens = jnp.asarray(toks[None, :])
            try:
                with self._mesh_ctx():
                    logits, self.cache = self._chunk_prefill(
                        self.params, tokens, self.cache, jnp.int32(i),
                        jnp.int32(n_real), jnp.int32(start))
            except Exception as e:
                self._finish_request(i, RequestState.FAILED,
                                     REASON_PREFILL_ERROR, error=e)
                raise
        self.prefill_dispatches += 1
        self._step_prefill_tokens += n_real
        if self.scheduler.advance(job, n_real):
            self.lengths[i] = job.p_len
            with span("sync.first_token"):
                req._next = int(jnp.argmax(logits[0]))
            if self.kv_pool is not None:
                # pages are final now — register them for prefix hits
                # (deferred from _try_admit; no-op for plain allocators)
                self.kv_pool.insert(req.prompt, self._slot_pages[i])
            req.state = RequestState.RUNNING
            self.admissions += 1
        else:
            # mid-prefill: lengths tracks the cursor so this lane's junk
            # decode scatter lands at the next chunk's start row
            self.lengths[i] = job.cursor

    def _note_step(self, decode_rows: int):
        """End-of-step bookkeeping: the prefill-token ledger (counters +
        scheduler step_log).  The ledger resets HERE, not at step start:
        direct ``add_request`` calls between steps prefill outside
        ``step()`` and their tokens belong to the step whose decode they
        delayed (the next one).  Gauges (queue depth, active slots, pool
        occupancy) are computed at scrape time by ``metrics_report``."""
        spent = self._step_prefill_tokens
        self._step_prefill_tokens = 0
        self.max_prefill_tokens_per_step = max(
            self.max_prefill_tokens_per_step, spent)
        if spent:
            self.counters["prefill_tokens"] += spent
        if self.scheduler is not None:
            self.scheduler.note_step(spent, decode_rows)

    def metrics_report(self) -> dict:
        """One JSON-able observability snapshot: lifecycle counters
        (merged with the registry's), live gauges, TTFT/ITL histogram
        percentiles, pool stats and the scheduler ledger.  This is what
        ``GET /metrics`` renders (serving.metrics.render_prometheus) and
        what the frontend benchmark asserts against."""
        snap = self.metrics.snapshot()
        counters = dict(self.counters)
        counters.update(snap["counters"])
        gauges = {
            "queue_depth": float(len(self.queue)),
            "active_slots": float(
                sum(s is not None and not s.done for s in self.slots)),
            "max_queue": float(self.max_queue),
            "prefill_compiles": float(self.prefill_compiles),
            "prefill_cache_hits": float(self.prefill_cache_hits),
            "max_prefill_tokens_per_step":
                float(self.max_prefill_tokens_per_step),
            "draining": float(self.draining),
        }
        report = {"counters": counters, "gauges": gauges,
                  "histograms": snap["histograms"]}
        if self.kv_pool is not None:
            report["kv_pool"] = self.kv_pool.stats()
        if self.scheduler is not None:
            report["scheduler"] = self.scheduler.report()
        if self.journal is not None:
            report["journal"] = self.journal.report()
        if self.watchdog is not None:
            report["watchdog"] = self.watchdog.report()
        return report

    def step(self) -> list[tuple[int, int]]:
        """One decode step for all active slots (each at its own cache
        position); returns (uid, token).

        A freshly prefilled slot first emits ``_next`` — the prefill's own
        argmax IS the first generated token (it used to be fed back but
        never emitted, shifting the stream by one) — then decodes.

        Lifecycle work rides the same call: deadlines expire first, then
        the bounded queue pumps admissions into free slots, then the
        decode dispatch crosses the 'decode' fault boundary (injected
        slow/transient/dispatch faults; poisoned rows).  A row whose
        logits are non-finite — really overflowed or injector-poisoned —
        quarantines ITS slot only: the victim lands FAILED(nan_logits)
        with no token emitted and the survivors' streams are untouched
        (decode is row-independent, so they stay bitwise-identical to a
        fault-free run under W4A16).

        Each phase runs inside a ``serve.*`` host span (``span``), so a
        profiler trace names what the host did in each device idle gap."""
        with span("step"):
            return self._step()

    def _step(self) -> list[tuple[int, int]]:
        t0 = self.clock()
        # the process_crash boundary fires BEFORE any state mutation: a
        # "crash between steps" leaves exactly the journal the previous
        # step's flush committed, which is what a SIGKILL leaves too
        act = self._fire("process_crash", scoped=False)
        if act is not None and act.error is not None:
            raise act.error
        with span("admit"):
            self._expire_deadlines()
            self._pump()
        if self.scheduler is not None:
            self._sched_run_chunk()
        with span("emit"):   # fresh prefills' first tokens; decode rows
            toks = np.zeros((self.batch_size,), np.int32)
            out = []
            active = []
            n_live = sum(r is not None for r in self.slots)
            self.max_concurrent = max(self.max_concurrent, n_live)
            for i, req in enumerate(self.slots):
                if req is None or req.done:
                    continue
                if req.state is RequestState.PREFILLING:
                    # a chunked admission mid-prefill holds its slot but
                    # does not decode; the batched dispatch's scatter for
                    # this lane writes a junk row at lengths[i] (= the chunk
                    # cursor), which the NEXT chunk overwrites before it is
                    # ever read
                    continue
                if not req.generated:
                    if req._next is None:
                        raise RuntimeError(
                            f"request {req.uid} occupies slot {i} but was "
                            "never prefilled (requests enter the batch via "
                            "add_request, which runs the admission "
                            "prefill)")
                    req.first_token_at = self.clock()
                    req._last_token_at = req.first_token_at
                    self.metrics.observe("ttft_ms", req.ttft_ms())
                    req.generated.append(req._next)
                    out.append((req.uid, req._next))
                    self._journal_append({"t": "token", "uid": req.uid,
                                          "tok": int(req._next)})
                    if len(req.generated) >= req.max_new_tokens:
                        self._finish_request(i, RequestState.FINISHED,
                                             REASON_MAX_NEW)
                        continue
                toks[i] = req.generated[-1]
                active.append(i)
        if not active:
            self._note_step(0)
            self._finish_step(t0)
            return out
        logits = self._guarded_decode(toks, active)
        # one vectorized argmax + host transfer per step, not one per
        # slot; the finiteness reduction rides the same device round-trip
        with span("sync.tokens"):
            next_toks = np.asarray(jnp.argmax(logits, axis=-1))
            nan_rows = np.asarray(jnp.any(~jnp.isfinite(logits), axis=-1))
        with span("emit"):
            now = self.clock()
            for i in active:
                req = self.slots[i]
                if req is None or req.done:
                    continue               # quarantined by a mid-step fault
                if req.uid in self._step_poison or bool(nan_rows[i]):
                    self._finish_request(i, RequestState.FAILED,
                                         REASON_NAN_LOGITS)
                    continue
                tok = int(next_toks[i])
                req.generated.append(tok)
                self.lengths[i] += 1
                out.append((req.uid, tok))
                self._journal_append({"t": "token", "uid": req.uid,
                                      "tok": tok})
                if req._last_token_at is not None:
                    self.metrics.observe("itl_ms",
                                         (now - req._last_token_at) * 1e3)
                req._last_token_at = now
                if len(req.generated) >= req.max_new_tokens:
                    self._finish_request(i, RequestState.FINISHED,
                                         REASON_MAX_NEW)
        self._note_step(len(active))
        self._finish_step(t0)
        return out

    def _finish_step(self, t0: float):
        """Step-boundary durability + liveness work: one journal flush
        commits the step's token records (the ``journal_sync='batch'``
        fsync point), then the watchdog hears the step's heartbeat and
        its verdicts run the degradation ladder."""
        with span("journal"):
            self._journal_flush()
            if self.watchdog is None:
                return
            verdict = self.watchdog.beat((self.clock() - t0) * 1e3)
            if verdict == "degrade":
                # first strikes ride the existing bitwise-preserving ladder
                # when a rung is armed; otherwise the strike just counts
                if self.act_quant == "mixfp4":
                    self._degrade_fused()
                self.counters["watchdog_degrades"] += 1
            elif verdict == "fail":
                self._watchdog_fail()

    def _watchdog_fail(self):
        """Past the degradation rung: fail the most starved in-flight
        request (longest since its last token — the one the hung steps
        are starving hardest) with the typed ``watchdog_timeout`` reason,
        releasing its slot and pool pages instead of wedging the batch."""
        live = [(i, r) for i, r in enumerate(self.slots)
                if r is not None and not r.done]
        if not live:
            return

        def anchor(req):
            if req._last_token_at is not None:
                return req._last_token_at
            return req.submitted_at if req.submitted_at is not None else 0.0

        i, _ = min(live, key=lambda ir: anchor(ir[1]))
        self._finish_request(i, RequestState.FAILED, REASON_WATCHDOG)
        self.counters["watchdog_fails"] += 1

    def _guarded_decode(self, toks, active):
        """The decode dispatch behind the 'decode' fault boundary.
        Injected 'slow' faults advance the clock (TTFT/deadline pressure),
        'dispatch' faults trigger the fused -> 2-pass degradation (or a
        backoff retry off the fused path), transients back off and retry,
        and a fatal fault quarantines its victim's slot, then decodes the
        survivors.  Its span records the rows decoded, the cache rows the
        occupied slots hold, and, paged, the pool pages reserved for them
        and the key blocks the attention kernel visits."""
        pool = {}
        if self.kv_pool is not None:
            pool["pages"] = self.kv_pool.pages_active
            if tracing():
                pool["kv_blocks"] = self._kv_blocks()
        held = sum(int(self.lengths[i]) for i, r in enumerate(self.slots)
                   if r is not None)
        with span("decode", rows=len(active), held_tokens=held, **pool):
            return self._decode_with_faults(toks, active)

    def _kv_blocks(self) -> int:
        """Key blocks the paged attention kernel visits in this decode
        step, over every lane and attention layer: a lane reads its length
        + 1 rows (an idle lane one), each layer within its own window."""
        page_len = self.kv_pool.page_len
        return paged_kv_blocks(self.lengths + 1, self._kv_windows,
                               self.max_len // page_len, page_len,
                               self.cfg.n_kv_heads, self.cfg.dh)

    def _decode_with_faults(self, toks, active):
        self._step_poison = set()
        attempt = 0
        spins = 0
        while True:
            spins += 1
            if spins > self.retry_max + self.batch_size + 8:
                raise RuntimeError(
                    "decode fault boundary did not converge (a schedule "
                    "that fires fatally on every occurrence can starve "
                    "the dispatch); refusing to spin")
            act = self._fire("decode", scoped=False)
            if act is not None:
                self._step_poison |= set(act.poison_uids)
                err = act.error
                if err is not None:
                    if err.kind == "dispatch" and self.act_quant == "mixfp4":
                        self._degrade_fused(err)
                        continue
                    if err.kind == "dispatch" or err.transient:
                        attempt += 1
                        if attempt > self.retry_max:
                            self.counters["retries_exhausted:decode"] += 1
                            raise err
                        self.counters["retries:decode"] += 1
                        self._sleep(self._backoff_s(attempt))
                        continue
                    # fatal, request-scoped (an injected host-transfer
                    # failure): quarantine the victim, decode the rest
                    victim = next(
                        (i for i in active
                         if self.slots[i] is not None
                         and self.slots[i].uid == err.uid), None)
                    if victim is not None:
                        self._finish_request(victim, RequestState.FAILED,
                                             REASON_INJECTED, error=err)
                    continue
            with self._mesh_ctx():
                logits, self.cache = self._decode(
                    self.params, jnp.asarray(toks), self.cache,
                    jnp.asarray(self.lengths.copy()))
            return logits

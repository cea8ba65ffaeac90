"""Sharding utilities: spec rewriting, sharding assembly, packed serving.

Three families of helpers live here (docs/sharding.md is the guide):

* **Spec rewriting** for the multi-pod production mesh —
  :func:`prepend_pod` rewrites every occurrence of the logical ``'data'``
  axis to ``('pod', 'data')`` so data parallelism spans pods while
  model/TP stays in-pod on ICI; :func:`sanitize_specs` makes a spec tree
  safe for *explicit* ``jit`` in_shardings, which (unlike internal
  ``with_sharding_constraint``s, where GSPMD pads) demand exact
  divisibility: any dim whose size is not divisible by the product of its
  assigned mesh axes is replicated, over-long specs are truncated to the
  leaf's rank, and short specs are right-padded with ``None``.
* **Train-step assembly** — :func:`make_train_shardings` turns (param
  specs, a batch template) into ``NamedSharding`` trees.
* **Packed serving** — :func:`serve_packed_specs` derives the engine's
  default TP layout for a packed weight tree (column-parallel N-sharding
  for 2-D QTensor stacks, expert-sharding for scan-stacked MoE stacks:
  both keep decode bitwise-identical to single-device, unlike K/row
  sharding which reassociates the reduction), and
  :func:`shard_packed_tree` / :func:`packed_restore_shardings` place a
  live tree / a checkpoint-restore skeleton under those specs with
  payload and scales co-sharded at 16-lane block granularity
  (``QTensor.with_sharding`` enforces the invariant).

:func:`shard_map` is the one wrapper every packed-operand collective
path uses (``jax.shard_map`` with the replication check off).
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import qtensor

__all__ = [
    "prepend_pod",
    "batch_spec",
    "make_train_shardings",
    "sanitize_specs",
    "shard_map",
    "serve_packed_specs",
    "shard_packed_tree",
    "packed_restore_shardings",
]


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off.

    Every in-repo use replicates operands over the axes a spec omits (the
    bodies are deterministic, so outputs really are replicated there), but
    the static replication checker cannot always prove it.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def prepend_pod(spec_tree):
    """Rewrite specs for the multi-pod mesh: every occurrence of the 'data'
    axis becomes ('pod', 'data') so DP spans pods.  Model/TP stays in-pod
    (ICI); only gradient reduction crosses the pod axis (DCI)."""
    def rw(spec):
        if spec is None:
            return spec
        parts = []
        for p in spec:
            if p == "data":
                parts.append(("pod", "data"))
            elif isinstance(p, tuple) and "data" in p:
                parts.append(tuple(
                    a for q in p for a in (("pod", "data") if q == "data"
                                           else (q,))))
            else:
                parts.append(p)
        return P(*parts)
    return jax.tree.map(rw, spec_tree,
                        is_leaf=lambda x: isinstance(x, P) or x is None)


def sanitize_specs(spec_tree, sds_tree, mesh):
    """Make a spec tree safe for explicit jit in_shardings against
    ``mesh``: replicate any dim whose size is not divisible by the product
    of its assigned mesh axes (explicit in_shardings demand exact
    divisibility, unlike internal constraints which GSPMD pads), truncate
    spec entries beyond the leaf's rank, and right-pad short specs with
    ``None``.  Tuple entries like ``('pod', 'data')`` divide by the axis
    product; ``None`` specs become fully-replicated ``P()``."""
    sizes = dict(mesh.shape)

    def axis_size(p):
        if p is None:
            return 1
        if isinstance(p, tuple):
            n = 1
            for a in p:
                n *= sizes[a]
            return n
        return sizes[p]

    def fix(spec, sd):
        if spec is None:
            return P()
        parts = list(spec)[: len(sd.shape)]
        parts += [None] * (len(sd.shape) - len(parts))
        for i, p in enumerate(parts):
            if p is not None and sd.shape[i] % axis_size(p) != 0:
                parts[i] = None
        return P(*parts)

    return jax.tree.map(fix, spec_tree, sds_tree,
                        is_leaf=lambda x: isinstance(x, P) or x is None)


def batch_spec(batch_like, multi_pod: bool = False):
    """Shard every batch leaf on dim 0 over the DP axes."""
    axes = ("pod", "data") if multi_pod else ("data",)
    def spec(x):
        return P(axes, *([None] * (x.ndim - 1)))
    return jax.tree.map(spec, batch_like)


def make_train_shardings(mesh, param_specs, batch_like, multi_pod=False):
    """NamedShardings for (params, batch) on ``mesh``."""
    pspecs = prepend_pod(param_specs) if multi_pod else param_specs
    to_sh = lambda s: NamedSharding(mesh, s if s is not None else P())
    param_sh = jax.tree.map(to_sh, pspecs,
                            is_leaf=lambda x: isinstance(x, P) or x is None)
    batch_sh = jax.tree.map(to_sh, batch_spec(batch_like, multi_pod))
    return param_sh, batch_sh


# ---------------------------------------------------------------------------
# Packed serving layout (docs/sharding.md)
# ---------------------------------------------------------------------------
_is_qt = lambda x: isinstance(x, qtensor.QTensor)


def serve_packed_specs(tree, mesh, *, model_axis: str = "model"):
    """Default TP layout for a packed serving weight tree: a logical
    ``PartitionSpec`` per QTensor leaf (``P()`` — replicated — for dense
    leaves: embeddings/norms are the paper's quantization exclusions).

    The layout is chosen so sharded decode stays *bitwise-identical* to
    the single-device packed path:

    * 2-D weight (stacks): shard the **N** (output) dim over
      ``model_axis`` — column-parallel; output columns are independent and
      the K tiling is unchanged, so no reduction is reassociated.  K/row
      sharding is supported by the contract (``qmm_sharded`` psums the
      partials) but not chosen by default, precisely because the psum
      reassociates the K reduction.
    * scan-stacked MoE expert stacks (≥2 leading batch dims on the
      children, ``(L, E, K, N)``): shard the **expert** dim — each device
      holds whole packed experts, K/N untouched.

    Dims that would violate 16-lane block granularity (or expert counts
    the axis does not divide) fall back to replication rather than error —
    the same leniency :func:`sanitize_specs` applies to dense specs.
    """
    msize = dict(mesh.shape).get(model_axis, 1)

    def qt_spec(qt):
        nb = qt._n_batch_dims()
        if not isinstance(qt.layout, qtensor.BlockLayout2D):
            return P()  # 1-D (KV-cache style) sharding: open ROADMAP item
        if nb >= 2:  # (L, E, K, N) expert stacks: shard whole experts
            if qt.payload.shape[nb - 1] % msize == 0:
                return P(*[None] * (nb - 1), model_axis, None, None)
            return P()
        np_ = qt.payload.shape[-1]
        if np_ % (msize * qt.layout.bn) == 0:
            return P(*[None] * nb, None, model_axis)
        return P()

    return jax.tree.map(lambda x: qt_spec(x) if _is_qt(x) else P(),
                        tree, is_leaf=_is_qt)


def shard_packed_tree(tree, spec_tree, mesh):
    """Place a packed weight tree onto ``mesh``: QTensor leaves via
    :meth:`QTensor.with_sharding` (payload/scales get co-sharded
    ``NamedSharding``s and the logical spec is recorded in the aux for
    mesh-aware ``qmm`` dispatch), dense leaves replicated (spec ``None``)
    or per their spec."""
    def place(leaf, spec):
        if _is_qt(leaf):
            return leaf.with_sharding(mesh, spec)
        return jax.device_put(
            leaf, NamedSharding(mesh, spec if spec is not None else P()))
    return jax.tree.map(place, tree, spec_tree, is_leaf=_is_qt)


def packed_restore_shardings(like_tree, spec_tree, mesh):
    """Shardings tree for restoring a packed checkpoint *directly* into
    the sharded layout (no replicated intermediate): ``like_tree`` is the
    :func:`repro.core.qtensor.tree_like` skeleton (ShapeDtypeStruct
    children), and every leaf position gets a ``NamedSharding`` —
    QTensor leaves the co-sharded child shardings, dense leaves their
    spec (replicated when ``None``)."""
    def sh(leaf, spec):
        if _is_qt(leaf):
            return leaf.shardings(mesh, spec)
        return NamedSharding(mesh, spec if spec is not None else P())
    return jax.tree.map(sh, like_tree, spec_tree, is_leaf=_is_qt)

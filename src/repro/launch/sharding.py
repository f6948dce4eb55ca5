"""Sharding rules: ModelConfig-aware NamedSharding assignment.

Parameter policies (DESIGN.md §5):

- ``tp``   — weights sharded over `model` only (heads / ffn / vocab /
             experts); replicated over the data axes. Used by the
             federated-simulation train mode (every data shard carries a
             full model replica for its clients) and by serving.
- ``fsdp`` — `tp` plus the largest remaining divisible axis sharded over
             the data axes (ZeRO-3); mandatory for qwen3-moe-235b and
             llama4-400b to fit 16 GB/chip.
- ``ep``   — expert-parallel serving (§Perf): expert tensors shard E over
             the DATA axes and F/D over `model` (tokens move via all-to-all
             instead of per-layer parameter all-gathers); non-expert
             tensors follow `tp`.
- ``dp``   — pure data parallel (§Perf, small models): weights fully
             replicated; pairs with sequence-sharded batches
             (train_batch_shardings seq_shard=True) so the `model` axis
             carries the SEQUENCE — per-layer comm drops from 4 activation
             all-reduces to 2 small k/v gathers.

Rules are name-based with a divisibility-checked fallback, so every leaf of
every architecture gets a legal spec.

The FL engine adds one more family (ARCHITECTURE.md §④): ``bank_spec`` /
``bank_shardings`` place a stacked CohortBank leaf — the leading (capacity,)
slot axis shards over the ``cohort`` mesh axis so independent cohorts live
on (and train on) their own devices; the per-slot remainder of the shape
follows the usual ``tp``/``dp`` policies above. ``row_sharding`` places the
round's flat participant-row axis over the same ``cohort`` axis so each
row's gather/aggregation against its cohort slot stays device-local.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import data_axes, data_size, model_size

# path fragments whose leaves get this many leading stacked-layer axes
_STACK2 = ("'mamba'", "'mlstm'")
_STACK1 = (
    "'blocks'",
    "'ssm_blocks'",
    "'attn_blocks'",
    "'dense_blocks'",
    "'moe_blocks'",
    "'mamba_tail'",
    "'slstm'",
)

# preferred model-sharded dim (negative index into the unstacked shape),
# first divisible one wins; positive names checked in order
_MODEL_RULES = (
    ("'heads'", (-1,)),  # musicgen heads (nc, D, V): V
    ("'embed'", (-2,)),  # (V, D) / (nc, V, D): V
    ("'head'", (-1,)),  # (D, V): V
    ("'wq'", (-2, 0)),
    # NEVER shard wk/wv on head_dim: RoPE splits hd in half and the SPMD
    # partitioner falls back to involuntary full rematerialization per layer
    # (measured: +16s/step collective on granite). KV heads if divisible,
    # else the d_model contraction dim (partial-sum all-reduce).
    ("'wk'", (-2, 0)),
    ("'wv'", (-2, 0)),
    ("'wo'", (0, -1)),  # (H, hd, D)
    ("'router'", ()),  # replicate router
    ("'wg'", (0, -1)),  # moe experts (E,D,F): E; dense mlp (D,F): F
    ("'wu'", (0, -1)),
    ("'wd'", (0,)),  # (F,D) or (E,F,D): F / E
    ("'w_in'", (-1, 0)),
    ("'conv_w'", (-1,)),
    ("'w_out'", (0,)),
    ("'w_up'", (-1, 0)),
    ("'w_down'", (0,)),
    ("'w_gates'", ()),
    ("'ffn_up'", (-1, 0)),
    ("'ffn_down'", (0,)),
    ("'r'", ()),
    ("'vis_proj'", (-1,)),
)


def _stack_ndims(keystr: str) -> int:
    if any(f in keystr for f in _STACK2):
        return 2
    if any(f in keystr for f in _STACK1):
        return 1
    return 0


def _moe_expert_leaf(keystr: str) -> bool:
    return "'moe'" in keystr and any(w in keystr for w in ("'wg'", "'wu'", "'wd'"))


def param_spec(keystr: str, shape: Tuple[int, ...], mesh, policy: str) -> P:
    """PartitionSpec for one parameter leaf."""
    if policy == "dp":
        return P()  # fully replicated weights
    msize = model_size(mesh)
    daxes = data_axes(mesh)
    dsize = data_size(mesh)

    stack = min(_stack_ndims(keystr), max(len(shape) - 1, 0))
    body = shape[stack:]
    spec: list = [None] * len(shape)

    # ---- model axis
    model_dim: Optional[int] = None
    candidates: Tuple[int, ...] = ()
    for name, dims in _MODEL_RULES:
        if name in keystr:
            candidates = dims
            break
    if _moe_expert_leaf(keystr):
        candidates = (0,)  # expert-parallel over E
        if policy == "ep":
            # serving EP: E over the data axes, F/D over model
            daxis = daxes if len(daxes) > 1 else daxes[0]
            especs = [None] * len(shape)
            if body[0] % dsize == 0 and body[0] >= dsize:
                especs[stack + 0] = daxis
            for di in (2, 1):
                if di < len(body) and body[di] % msize == 0 and body[di] >= msize:
                    especs[stack + di] = "model"
                    break
            return P(*especs)
    for d in candidates:
        di = d if d >= 0 else len(body) + d
        if 0 <= di < len(body) and body[di] % msize == 0 and body[di] >= msize:
            model_dim = di
            break
    if model_dim is None and not candidates == () and len(body) > 0:
        # fallback: largest divisible dim, scanned from the end
        order = sorted(range(len(body)), key=lambda i: (-body[i],))
        for di in order:
            if body[di] % msize == 0 and body[di] >= msize * 8:
                model_dim = di
                break
    if model_dim is not None:
        spec[stack + model_dim] = "model"

    # ---- fsdp: shard one more axis over the data axes
    if policy == "fsdp" and len(body) > 0:
        order = sorted(range(len(body)), key=lambda i: (-body[i],))
        for di in order:
            if spec[stack + di] is not None:
                continue
            if body[di] % dsize == 0 and body[di] >= dsize:
                spec[stack + di] = daxes if len(daxes) > 1 else daxes[0]
                break

    return P(*spec)


def param_shardings(shapes: Any, mesh, policy: str = "tp"):
    """Map an eval_shape'd param pytree -> NamedSharding pytree."""

    def one(path, leaf):
        ks = jax.tree_util.keystr(path)
        return NamedSharding(mesh, param_spec(ks, leaf.shape, mesh, policy))

    return jax.tree_util.tree_map_with_path(one, shapes)


def batch_spec(shape: Tuple[int, ...], mesh, batch_dim: int = 0) -> P:
    """Shard the leading (client/batch) dim over the data axes."""
    daxes = data_axes(mesh)
    dsize = data_size(mesh)
    spec: list = [None] * len(shape)
    if shape and shape[batch_dim] % dsize == 0 and shape[batch_dim] >= dsize:
        spec[batch_dim] = daxes if len(daxes) > 1 else daxes[0]
    return P(*spec)


def batch_shardings(shapes: Any, mesh, seq_shard: bool = False):
    """seq_shard: also shard the SEQUENCE axis over `model` (dp_seq policy).
    The sequence axis is the last (tokens) or second-to-last (embeddings)."""
    msize = model_size(mesh)

    def one(l):
        spec = list(batch_spec(l.shape, mesh))
        if seq_shard:
            sdim = len(l.shape) - 1
            if l.dtype not in (jnp.int32, jnp.int64):  # embeddings: (..., P, D)
                sdim = len(l.shape) - 2
            if (
                sdim > 0
                and spec[sdim] is None
                and l.shape[sdim] % msize == 0
                and l.shape[sdim] >= msize
            ):
                spec[sdim] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(one, shapes)


def cache_spec(shape: Tuple[int, ...], global_batch: int, mesh,
               seq_shard: bool = False) -> P:
    """KV/recurrent cache leaf: batch dim -> data axes, then one more
    divisible dim -> model.

    seq_shard=False (baseline): prefer the trailing head dims for `model`.
    seq_shard=True (§Perf): prefer the LARGEST divisible dim — for KV
    caches that is the sequence axis, giving flash-decode-style partial
    attention instead of gathering the cache when kv_heads < model size.
    """
    daxes = data_axes(mesh)
    dsize = data_size(mesh)
    msize = model_size(mesh)
    spec: list = [None] * len(shape)
    # scan-stacked caches have 1-2 leading layer dims; find the batch dim by
    # value match instead of position.
    bdim = None
    for i, s in enumerate(shape):
        if s == global_batch and global_batch % dsize == 0 and global_batch >= dsize:
            bdim = i
            spec[i] = daxes if len(daxes) > 1 else daxes[0]
            break
    mdim = None
    order = (
        sorted(range(len(shape)), key=lambda i: -shape[i])
        if seq_shard
        else list(range(len(shape) - 1, -1, -1))
    )
    for i in order:
        if i == bdim or spec[i] is not None:
            continue
        if shape[i] % msize == 0 and shape[i] >= msize:
            mdim = i
            spec[i] = "model"
            break
    if bdim is None:
        # batch-1 decode: give the data axes to the largest remaining dim
        for i in sorted(range(len(shape)), key=lambda j: -shape[j]):
            if spec[i] is None and shape[i] % dsize == 0 and shape[i] >= dsize * 8:
                spec[i] = daxes if len(daxes) > 1 else daxes[0]
                break
    return P(*spec)


def cache_shardings(shapes: Any, global_batch: int, mesh, seq_shard: bool = False):
    return jax.tree.map(
        lambda l: NamedSharding(mesh, cache_spec(l.shape, global_batch, mesh, seq_shard)),
        shapes,
    )


def replicated(mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# CohortBank placement: slot axis -> cohort mesh axis (ARCHITECTURE.md §④)
# ---------------------------------------------------------------------------
def bank_spec(keystr: str, shape: Tuple[int, ...], mesh, policy: str = "dp") -> P:
    """PartitionSpec for one stacked CohortBank leaf.

    shape[0] is the bank's slot (capacity) axis — sharded over ``cohort``;
    shape[1:] is one cohort model's leaf, sharded *within* the slot by the
    usual ``param_spec`` policy when the mesh carries a ``model`` axis
    (``tp``/``fsdp``), or replicated per slot under ``dp``.
    """
    if len(shape) == 0:
        return P()
    inner: Tuple = (None,) * (len(shape) - 1)
    if policy != "dp" and "model" in mesh.axis_names and len(shape) > 1:
        inner = tuple(param_spec(keystr, shape[1:], mesh, policy))
        inner = inner + (None,) * (len(shape) - 1 - len(inner))
    # normalize away trailing Nones: P("cohort") and P("cohort", None, ...)
    # are the same placement but UNEQUAL to the jit cache — a bank entering
    # a step under one spelling and leaving under the other would silently
    # retrace (shard_map out_specs use the short form)
    while inner and inner[-1] is None:
        inner = inner[:-1]
    return P("cohort", *inner)


def bank_shardings(shapes: Any, mesh, policy: str = "dp"):
    """Map a stacked-bank pytree (leaves ``(capacity, ...)``) to
    NamedShardings: slot axis over ``cohort``, per-slot dims by `policy`."""

    def one(path, leaf):
        ks = jax.tree_util.keystr(path)
        return NamedSharding(mesh, bank_spec(ks, leaf.shape, mesh, policy))

    return jax.tree_util.tree_map_with_path(one, shapes)


def row_sharding(mesh):
    """Sharding for the round's flat participant-row axis: rows live on the
    device that owns their cohort's bank slot (block-aligned by the
    MatchPlan packing), so per-row gathers and the masked segment-sum
    aggregation never cross the mesh."""
    return NamedSharding(mesh, P("cohort"))


# ---------------------------------------------------------------------------
# Elastic remesh (ARCHITECTURE.md §⑨): re-pack bank slots to a new shard count
# ---------------------------------------------------------------------------
# The CohortBank allocates slot n -> (n % S)·slots_per_shard + n // S, so a
# cohort's SLOT ID is a function of the shard count. Restoring a run onto a
# different `cohort_shards` therefore permutes the live slots: the canonical
# key that survives a remesh is the ALLOCATION index (0 = root, then
# partition order). These helpers map allocation order <-> slot layout and
# re-pack stacked per-slot state between layouts — the inverse discipline of
# `spawn_children`'s scatter, with `out_shardings` (from bank_shardings /
# bank_spec) pinning the target placement so the restored bank enters the
# fused round step under its compile-time sharding.


def padded_capacity(capacity: int, n_shards: int) -> int:
    """Bank capacity after shard padding (every device owns an equal block)."""
    n_shards = max(1, int(n_shards))
    return -(-int(capacity) // n_shards) * n_shards


def alloc_slots(n_alloc: int, capacity: int, n_shards: int) -> np.ndarray:
    """Slot ids of allocations 0..n_alloc-1 under the bank's round-robin
    placement (mirrors ``CohortBank._alloc_slot`` after shard padding).
    Idempotent in `capacity`: padding an already-padded capacity is a no-op.
    """
    n_shards = max(1, int(n_shards))
    cap = padded_capacity(capacity, n_shards)
    assert n_alloc <= cap, (n_alloc, cap)
    n = np.arange(int(n_alloc), dtype=np.int64)
    if n_shards == 1:
        return n
    sps = cap // n_shards
    return (n % n_shards) * sps + n // n_shards


def repack_permutation(
    n_alloc: int, capacity: int, old_shards: int, new_shards: int
) -> Tuple[np.ndarray, np.ndarray]:
    """(old_slots, new_slots): where allocation n lived under `old_shards`
    and where it lands under `new_shards`. Both are injective (each a
    permutation of the live allocations into their layout's slot space), so
    a re-pack through them loses and duplicates nothing."""
    return (
        alloc_slots(n_alloc, capacity, old_shards),
        alloc_slots(n_alloc, capacity, new_shards),
    )


def gather_allocations(tree: Any, old_slots: np.ndarray) -> Any:
    """Canonical per-allocation view of a stacked (capacity, ...) pytree:
    leaf[old_slots] as host numpy arrays (allocation order, layout-free)."""
    idx = np.asarray(old_slots)
    return jax.tree.map(lambda a: np.asarray(a)[idx], tree)


def scatter_allocations(tree: Any, canonical: Any, new_slots, out_shardings=None):
    """Write canonical per-allocation leaves into a stacked tree at
    `new_slots`. With `out_shardings` (a bank_shardings pytree) the scatter
    is jitted with the target placement PINNED — same discipline as
    ``CohortBank.spawn_children`` — so the result's sharding cannot drift
    from the bank's compile-time specs."""
    idx = jnp.asarray(np.asarray(new_slots))

    def fn(t, c):
        return jax.tree.map(lambda a, v: a.at[idx].set(v), t, c)

    if out_shardings is not None:
        return jax.jit(fn, out_shardings=out_shardings)(tree, canonical)
    return jax.jit(fn)(tree, canonical)


def repack_stacked(
    tree: Any,
    capacity: int,
    n_alloc: int,
    old_shards: int,
    new_shards: int,
    out_shardings=None,
) -> Any:
    """Re-pack a stacked (old padded capacity, ...) pytree into the slot
    layout of `new_shards`: gather live allocations from the old layout,
    scatter them into a default-initialized tree of the new padded
    capacity. Slots no allocation maps to hold zeros — exactly the state
    of a freshly-constructed bank's unallocated slots."""
    old_slots, new_slots = repack_permutation(
        n_alloc, capacity, old_shards, new_shards
    )
    canonical = gather_allocations(tree, old_slots)
    new_cap = padded_capacity(capacity, new_shards)
    target = jax.tree.map(
        lambda a: jnp.zeros((new_cap,) + np.asarray(a).shape[1:],
                            np.asarray(a).dtype),
        tree,
    )
    return scatter_allocations(target, canonical, new_slots, out_shardings)

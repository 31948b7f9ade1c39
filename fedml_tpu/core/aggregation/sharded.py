"""Mesh-sharded bucketed aggregation: the server data plane over N chips.

``BucketedAggregator`` (PR 1) holds the whole f32 accumulator, the FedOpt
optimizer state, and the finalized model on ONE device — HBM high-water
scales with model size, which is what kills ``llm_xla`` on a single chip.
This engine lays the flat-vector dtype-group accumulator out over a named
mesh instead (the weight-update sharding of Xu et al., "Automatic
Cross-Replica Sharding of Weight Update in Data-Parallel Training", applied
to the *federated* server step):

- **Layout.** Each client delta is flattened into one contiguous vector per
  dtype group, zero-padded to a multiple of the shard count, and laid out
  with an fsdp-style even split (``NamedSharding`` over all mesh axes).
  Specs are derived ONCE per (treedef, shapes, dtypes) template and cached.
- **Accumulation.** Buckets of client vectors are contracted shard-wise in
  one jitted step with the f32 accumulator DONATED — the contraction has no
  cross-shard terms (weights are replicated, the vector dim is sharded), so
  each device touches only its 1/N slice and no collective runs per bucket.
- **Ingestion overlap (PiPar).** Host flat deltas are sliced per-shard by
  ``jax.device_put`` against the vector sharding — an async dispatch — and
  the aggregate loop is double-buffered: bucket ``i+1``'s transfer is issued
  before bucket ``i``'s accumulation, so PCIe rides under compute instead of
  barriering on it.
- **Fused round step.** :class:`ShardedFedOptServer` fuses finalize (f32 →
  param dtype), the FedOpt pseudo-gradient step, and the broadcast
  materialization source into ONE donated jitted sharded call over the flat
  groups: params and optimizer state live sharded across rounds, and the
  full model only ever assembles on the HOST (one device→host fetch per
  dtype group) for the WAN broadcast — never replicated on a chip. Eval
  reads :meth:`ShardedBucketedAggregator.tree_view` — leaves rebuilt
  on-device WITH shardings — so the eval step runs sharded too.

``jax.device_get`` is banned in this file (``tools/check_sharding.py``): the
only full-model gather is the host-side broadcast materialization, which
rides ``np.asarray`` per dtype group and books its bytes via
``record_transfer``.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import telemetry as tel
from ..distributed import mesh as dmesh
from .bucketed import BucketedAggregator, _is_object_leaf, _object_fold

PyTree = Any


class _Group:
    """One dtype group of the flat layout: which leaves, where they sit in
    the flat vector, and the padded/sharded geometry."""

    __slots__ = ("dtype", "leaf_idx", "offsets", "sizes", "size", "padded")

    def __init__(self, dtype, leaf_idx: List[int], offsets: List[int],
                 sizes: List[int], size: int, padded: int):
        self.dtype = dtype
        self.leaf_idx = leaf_idx
        self.offsets = offsets
        self.sizes = sizes
        self.size = size
        self.padded = padded


class ShardLayout:
    """Flat-vector dtype-group layout + NamedSharding specs for one template
    (derived once per (treedef, shapes, dtypes) and cached on the engine)."""

    def __init__(self, template: PyTree, mesh):
        leaves, self.treedef = jax.tree.flatten(template)
        self.shapes = tuple(tuple(np.shape(l)) for l in leaves)
        self.dtypes = tuple(
            np.dtype(getattr(l, "dtype", None) or np.asarray(l).dtype) for l in leaves)
        self.key = (self.treedef, self.shapes, self.dtypes)
        self.mesh = mesh
        self.n_shards = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        axes = tuple(mesh.axis_names)
        # flat vectors: dim 0 split over every mesh axis (fsdp-style)
        self.vec_sharding = NamedSharding(mesh, P(axes))
        self.repl_sharding = NamedSharding(mesh, P())
        self.groups: Dict[str, _Group] = {}
        order: Dict[str, List[int]] = {}
        for i, dt in enumerate(self.dtypes):
            order.setdefault(dt.name, []).append(i)
        for name in sorted(order):
            idxs = order[name]
            sizes = [int(np.prod(self.shapes[i])) if self.shapes[i] else 1 for i in idxs]
            offsets, off = [], 0
            for s in sizes:
                offsets.append(off)
                off += s
            padded = -(-off // self.n_shards) * self.n_shards  # ceil to shard multiple
            self.groups[name] = _Group(np.dtype(name), idxs, offsets, sizes, off, padded)
        # per-leaf shardings for tree_view: shard dim 0 when it divides evenly,
        # else replicate (small leaves — biases, norms — cost nothing)
        self.leaf_shardings = []
        for shp in self.shapes:
            if shp and shp[0] % self.n_shards == 0 and shp[0] > 0:
                self.leaf_shardings.append(NamedSharding(mesh, P(axes)))
            else:
                self.leaf_shardings.append(self.repl_sharding)

    def shard_bytes(self, dtype_override=None) -> int:
        """Resident bytes PER DEVICE for one set of group vectors."""
        total = 0
        for g in self.groups.values():
            itemsize = np.dtype(dtype_override).itemsize if dtype_override else g.dtype.itemsize
            total += (g.padded // self.n_shards) * itemsize
        return total


class ShardedDelta:
    """A client delta already resident on the mesh as sharded flat group
    vectors (produced by :meth:`ShardedBucketedAggregator.ingest` at arrival
    time, so upload overlaps the round instead of serializing into it)."""

    __slots__ = ("layout_key", "groups", "nbytes")

    def __init__(self, layout_key, groups: Dict[str, jax.Array], nbytes: int):
        self.layout_key = layout_key
        self.groups = groups
        self.nbytes = nbytes


class ShardedBucketedAggregator(BucketedAggregator):
    """Drop-in for :class:`BucketedAggregator` with the accumulator, bucket
    chunks, and finalized model laid out over ``mesh``. Falls back to the
    object-leaf host fold exactly like the base engine."""

    # the sharded fold has no fused watch variant yet (stats would need a
    # per-shard reduction); callers gate on this and skip modelwatch here
    supports_watch = False

    def __init__(self, bucket_size: int, mesh):
        super().__init__(bucket_size)
        self.mesh = mesh
        self.sharded_traces = 0
        self._layouts: Dict[Any, ShardLayout] = {}
        self._saccum_first = jax.jit(
            tel.track_compiles(self._saccum_first_impl, name="agg_accum_sharded"))
        self._saccum = jax.jit(
            tel.track_compiles(self._saccum_impl, name="agg_accum_sharded"),
            donate_argnums=(0,))
        self._flatten_dev_cache: Dict[Any, Any] = {}
        self._view_cache: Dict[Any, Any] = {}
        dmesh.note_mesh("server_agg", mesh)

    # --- layout -----------------------------------------------------------
    def layout_for(self, template: PyTree) -> ShardLayout:
        leaves, treedef = jax.tree.flatten(template)
        shapes = tuple(tuple(np.shape(l)) for l in leaves)
        dtypes = tuple(np.dtype(getattr(l, "dtype", None) or np.asarray(l).dtype) for l in leaves)
        key = (treedef, shapes, dtypes)
        layout = self._layouts.get(key)
        if layout is None:
            layout = self._layouts[key] = ShardLayout(template, self.mesh)
            per_dev = layout.shard_bytes(np.float32)  # the f32 accumulator
            dmesh.record_shard_bytes(
                "agg_accumulator",
                {str(d): per_dev for d in self.mesh.devices.flat})
        return layout

    # --- ingestion (host -> per-shard stream) -----------------------------
    def _flatten_host(self, tree: PyTree, layout: ShardLayout) -> Dict[str, np.ndarray]:
        """Host-side slice of a delta into padded per-group flat vectors."""
        leaves = jax.tree.leaves(tree)
        out: Dict[str, np.ndarray] = {}
        for name, g in layout.groups.items():
            vec = np.zeros((g.padded,), g.dtype)  # zero pad -> pads never pollute acc
            for i, off, size in zip(g.leaf_idx, g.offsets, g.sizes):
                vec[off:off + size] = np.ravel(np.asarray(leaves[i]))  # fedlint: disable=host-sync host-slicing ingest IS the host path: one copy per delta leaf, feeding per-shard device_put
            out[name] = vec
        return out

    def _flatten_device_fn(self, layout: ShardLayout, to_f32: bool = False):
        """Jitted device-tree -> sharded group vectors (a device-side
        reshard; used when deltas already live on device, e.g. the sp path)."""
        key = (layout.key, to_f32)
        fn = self._flatten_dev_cache.get(key)
        if fn is None:
            def build(tree):
                leaves = jax.tree.leaves(tree)
                out = {}
                for name, g in layout.groups.items():
                    parts = [jnp.ravel(leaves[i]) for i in g.leaf_idx]
                    if g.padded > g.size:
                        parts.append(jnp.zeros((g.padded - g.size,), g.dtype))
                    vec = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
                    out[name] = vec.astype(jnp.float32) if to_f32 else vec
                return out
            shardings = {name: layout.vec_sharding for name in layout.groups}
            fn = self._flatten_dev_cache[key] = jax.jit(build, out_shardings=shardings)
        return fn

    def ingest(self, tree: PyTree, template: Optional[PyTree] = None) -> ShardedDelta:
        """Upload one delta as sharded flat group vectors.

        Host leaves are sliced host-side and ``device_put`` against the
        vector sharding — jax splits the flat vector per shard and issues the
        per-device copies asynchronously, so the call returns before the
        transfer lands and overlaps whatever the mesh is computing (the
        PiPar-style ingestion stream). Device leaves take a jitted reshard.
        """
        layout = self.layout_for(template if template is not None else tree)
        leaves = jax.tree.leaves(tree)
        on_device = all(
            isinstance(l, jnp.ndarray) and not isinstance(l, np.ndarray) for l in leaves)
        if on_device:
            groups = self._flatten_device_fn(layout)(tree)
            nbytes = sum(int(v.nbytes) for v in groups.values())
        else:
            host = self._flatten_host(tree, layout)
            groups = {}
            nbytes = 0
            for name, vec in host.items():
                groups[name] = jax.device_put(vec, layout.vec_sharding)
                nbytes += vec.nbytes
            tel.record_transfer("host_to_device", nbytes)
        return ShardedDelta(layout.key, groups, nbytes)

    # --- jitted bucket step -----------------------------------------------
    def _sbucket_sum(self, chunk, weights):
        # stack-inside-jit, per dtype group: [b, padded] sharded on the vector
        # dim; weights replicated -> the contraction is purely shard-local
        def group_sum(name):
            stacked = jnp.stack([c[name].astype(jnp.float32) for c in chunk])
            return jnp.tensordot(weights, stacked, axes=((0,), (0,)))
        return {name: group_sum(name) for name in chunk[0]}

    def _saccum_first_impl(self, chunk, weights):
        self.accum_traces += 1  # trace-time only (same contract as the base)
        self.sharded_traces += 1
        return self._sbucket_sum(chunk, weights)

    def _saccum_impl(self, acc, chunk, weights):
        self.accum_traces += 1
        self.sharded_traces += 1
        contrib = self._sbucket_sum(chunk, weights)
        return {name: acc[name] + contrib[name] for name in acc}

    def _ingest_bucket(self, bucket, layout: ShardLayout):
        trees, w = bucket
        chunk = []
        for t in trees:
            if isinstance(t, ShardedDelta):
                if t.layout_key != layout.key:
                    raise ValueError("ShardedDelta layout does not match this cohort's template")
                chunk.append(t.groups)
            else:
                chunk.append(self.ingest(t).groups)
        weights = jax.device_put(np.asarray(w, np.float32), layout.repl_sharding)
        return tuple(chunk), weights

    # --- finalize / views --------------------------------------------------
    def _finalize_sharded_fn(self, layout: ShardLayout):
        """Jitted f32 group vecs -> template tree, leaves cast + resharded
        per-leaf (dim 0 split where it divides; small leaves replicated)."""
        return self._unflatten_fn(layout, from_f32=True)

    def _unflatten_fn(self, layout: ShardLayout, from_f32: bool):
        key = (layout.key, from_f32)
        fn = self._view_cache.get(key)
        if fn is None:
            def build(groups):
                leaves: List[Any] = [None] * len(layout.shapes)
                for name, g in layout.groups.items():
                    vec = groups[name]
                    for i, off, size in zip(g.leaf_idx, g.offsets, g.sizes):
                        leaf = vec[off:off + size].reshape(layout.shapes[i])
                        leaves[i] = leaf.astype(layout.dtypes[i]) if from_f32 else leaf
                return jax.tree.unflatten(layout.treedef, leaves)
            out_shardings = jax.tree.unflatten(layout.treedef, list(layout.leaf_shardings))
            fn = self._view_cache[key] = jax.jit(build, out_shardings=out_shardings)
        return fn

    def tree_view(self, groups: Dict[str, jax.Array], layout: ShardLayout) -> PyTree:
        """Rebuild the template tree on-device from native-dtype group vecs —
        leaves keep shardings, so eval steps on the result run sharded."""
        return self._unflatten_fn(layout, from_f32=False)(groups)

    def host_tree(self, groups: Dict[str, jax.Array], layout: ShardLayout) -> PyTree:
        """Broadcast materialization: ONE device->host fetch per dtype group
        (np.asarray gathers the addressable shards), then host-side views per
        leaf. The full model assembles on the host, never on a chip."""
        leaves: List[Any] = [None] * len(layout.shapes)
        for name, g in layout.groups.items():
            host = np.asarray(groups[name])  # fedlint: disable=host-sync THE sanctioned broadcast gather: once per dtype group, byte-booked below
            tel.record_transfer("device_to_host", host.nbytes)
            for i, off, size in zip(g.leaf_idx, g.offsets, g.sizes):
                leaves[i] = host[off:off + size].reshape(layout.shapes[i])
        return jax.tree.unflatten(layout.treedef, leaves)

    # --- public entry points ----------------------------------------------
    def aggregate(self, pairs: Sequence[Tuple[float, PyTree]]) -> PyTree:
        return self.aggregate_round(pairs, server=None)

    def aggregate_round(self, pairs: Sequence[Tuple[float, PyTree]],
                        server: Optional["ShardedFedOptServer"] = None) -> PyTree:
        """Weighted average of ``(weight, tree_or_ShardedDelta)`` pairs over
        the mesh; with ``server`` the finalize fuses into its round step and
        the NEW GLOBAL PARAMS come back (sharded leaves)."""
        if not pairs:
            raise ValueError("aggregate() needs at least one (weight, tree) pair")
        weights = np.asarray([float(w) for w, _ in pairs], dtype=np.float32)
        weights = weights / weights.sum()
        trees = [t for _, t in pairs]
        first = trees[0]
        if not isinstance(first, ShardedDelta) and any(
                _is_object_leaf(l) for l in jax.tree.leaves(first)):
            if server is not None:
                raise ValueError("object-leaf cohorts cannot ride the fused sharded round step")
            return _object_fold(trees, weights)
        if isinstance(first, ShardedDelta):
            layout = self._layouts[first.layout_key]
        else:
            layout = self.layout_for(first)
        b = self.bucket_size
        with tel.span("agg.aggregate_sharded", k=len(trees), bucket_size=b,
                      shards=layout.n_shards):
            buckets = []
            for start in range(0, len(trees), b):
                chunk = trees[start:start + b]
                w = weights[start:start + b]
                if len(chunk) < b:  # ragged tail: zero-weight pad to bucket shape
                    pad = b - len(chunk)
                    chunk = list(chunk) + [chunk[-1]] * pad
                    w = np.concatenate([w, np.zeros((pad,), np.float32)])
                buckets.append((chunk, w))
            # double buffer: bucket i+1's per-shard device_put is issued
            # before bucket i's accumulation so transfer overlaps compute
            pending = self._ingest_bucket(buckets[0], layout)
            acc = None
            for i in range(len(buckets)):
                cur = pending
                pending = (self._ingest_bucket(buckets[i + 1], layout)  # fedlint: disable=interproc-host-sync double-buffered ingest: the host-side staging copy's device_put deliberately overlaps bucket i's accumulation
                           if i + 1 < len(buckets) else None)
                with tel.span("agg.bucket_sharded", bucket_size=b, first=acc is None):
                    if acc is None:
                        acc = self._saccum_first(*cur)
                    else:
                        acc = self._saccum(acc, *cur)
            if server is not None:
                return server.round_step(acc)
            with tel.span("agg.finalize"):
                return self._finalize_sharded_fn(layout)(acc)


class ShardedFedOptServer:
    """FedOpt server state held as SHARDED flat group vectors.

    Drop-in for ``server_optimizer.FedOptServer`` (:meth:`apply` keeps the
    ``(w_global, w_avg) -> new_params`` contract) plus the fused
    :meth:`round_step`: finalize + pseudo-gradient + optimizer update in one
    donated jitted sharded call, so params + optimizer state never exist
    replicated on a chip.
    """

    def __init__(self, args: Any, params_template: PyTree,
                 engine: ShardedBucketedAggregator):
        from .server_optimizer import create_server_optimizer

        if not isinstance(engine, ShardedBucketedAggregator):
            raise TypeError("ShardedFedOptServer needs a ShardedBucketedAggregator")
        self.engine = engine
        self.layout = engine.layout_for(params_template)
        self.tx = create_server_optimizer(args)
        self.round_traces = 0
        # params live as native-dtype sharded group vecs from day one
        self._params_groups = engine.ingest(params_template).groups
        # explicit out_shardings (as parallel/fsdp.py does): zeros_like does
        # not inherit the input's NamedSharding, and moments left on device 0
        # cost a retrace once round 1's outputs come back sharded
        self._state = jax.jit(
            self.tx.init,
            out_shardings=jax.tree.map(
                self._state_sharding,
                jax.eval_shape(self.tx.init, self._params_groups)),
        )(self._params_groups)
        self._book_shard_bytes()

        def _round(params_g, acc_g, opt_state):
            self.round_traces += 1  # trace-time only
            # fused finalize: the normalized f32 weighted sum casts straight
            # into param dtype; no separate finalized-average array persists
            avg_g = {n: acc_g[n].astype(params_g[n].dtype) for n in params_g}
            pseudo = {n: params_g[n] - avg_g[n] for n in params_g}  # -delta
            updates, new_state = self.tx.update(pseudo, opt_state, params_g)
            new_params = {n: params_g[n] + updates[n].astype(params_g[n].dtype)
                          for n in params_g}
            return new_params, new_state

        self._round = jax.jit(
            tel.track_compiles(_round, name="agg_round_step"),
            donate_argnums=(0, 1, 2))

    @property
    def state(self):
        """Optimizer state pytree (FedOptServer-compatible attribute). The
        setter re-shards host leaves — crash-resume restores checkpointed
        state as numpy, which must re-enter as sharded group vectors or the
        next round step would recompile against replicated inputs."""
        return self._state

    @state.setter
    def state(self, value):
        def put(v):
            if isinstance(v, jnp.ndarray) and not isinstance(v, np.ndarray):
                return v
            arr = np.asarray(v)
            return jax.device_put(arr, self._state_sharding(arr))

        self._state = jax.tree.map(put, value)

    def _state_sharding(self, leaf):
        """Optimizer-state leaves that mirror a padded group vector (moments)
        shard like it; everything else (step counts) replicates."""
        shape = tuple(leaf.shape)
        padded = {g.padded for g in self.layout.groups.values()}
        if len(shape) == 1 and shape[0] in padded:
            return self.layout.vec_sharding
        return self.layout.repl_sharding

    def _book_shard_bytes(self) -> None:
        layout = self.layout
        per_dev = layout.shard_bytes()  # params (native dtype)
        per_dev += sum(  # optimizer state slots (momentum/nu/...)
            (int(l.size) // max(1, layout.n_shards)) * l.dtype.itemsize
            for l in jax.tree.leaves(self.state)
            if hasattr(l, "size") and hasattr(l, "dtype"))
        dmesh.record_shard_bytes(
            "fedopt_server",
            {str(d): per_dev for d in layout.mesh.devices.flat})

    def params_view(self) -> PyTree:
        """The current global params as the sharded tree view every round
        step returns. Round loops start from THIS (not the caller's
        single-device tree) so round 0 trains and evaluates on the same
        layout as every later round — one compile of the local step, and no
        first round with the whole model on device 0."""
        return self.engine.tree_view(self._params_groups, self.layout)

    def round_step(self, acc_groups: Dict[str, jax.Array]) -> PyTree:
        """Fused finalize + FedOpt step over a DONATED f32 accumulator; the
        new global params come back as a sharded tree view for eval, and
        :meth:`materialize_broadcast` serves the host copy for the WAN."""
        with tel.span("agg.round_step_sharded", shards=self.layout.n_shards):
            self._params_groups, self.state = self._round(
                self._params_groups, acc_groups, self.state)
            return self.params_view()

    def apply(self, w_global: PyTree, w_avg: PyTree) -> PyTree:
        """FedOptServer-compatible entry: reshard the caller's trees into
        flat groups (device-side, jitted) and run the same fused step."""
        params_g = self.engine._flatten_device_fn(self.layout)(w_global)
        acc_g = self.engine._flatten_device_fn(self.layout, to_f32=True)(w_avg)
        self._params_groups, self.state = self._round(params_g, acc_g, self.state)
        return self.params_view()

    def materialize_broadcast(self) -> PyTree:
        """Host numpy tree of the current global params (one fetch per dtype
        group) — the only place the full model assembles, and it is RAM."""
        return self.engine.host_tree(self._params_groups, self.layout)

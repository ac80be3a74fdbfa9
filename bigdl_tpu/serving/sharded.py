"""Sharded serving plane: the pooled prefill/decode/sample programs on a
device mesh.

PRs 1–3 made the serving stack SHAPE-STABLE end to end — bucketed batch
prefill, pooled per-row decode, per-row sampling, all runtime data of a
bounded compiled-program set. That is exactly the property that lets the
same programs scale ACROSS chips (the BigDL thesis transplanted to
inference: partition one logical job over workers with explicit
collectives, arXiv:1804.05839; and the MLPerf-on-TPU-pods recipe: keep
ONE compiled program and grow the mesh, arXiv:1909.09756). This module
is that step. Two composable axes over one
``jax.sharding.Mesh(("data", "model"))``:

* **slot data parallelism** (``data`` axis) — the pooled KV carry
  shards along its SLOT axis: with N data shards each device owns
  ``n_slots/N`` decode rows, and the engine's one
  ``get_batch_decode_step`` invocation steps the whole fleet. Rows
  never interact (per-row attention over the row's own cache; per-row
  sampling lanes, penalty counts, and knob arrays shard with their
  rows for free), so the partitioned program computes BITWISE the same
  per-row math as the single-device engine — sharded serving is
  token-identical, not merely close (pinned by
  tests/test_serving_sharded.py). XLA's SPMD partitioner does the
  splitting: no shard_map, no new program per occupancy, ONE compiled
  step per engine regardless of mesh size. (One exception, on a TPU:
  the decode step's Pallas attention kernel cannot be partitioned
  automatically, so the step — the target's, and a speculative
  draft's on every plane — takes the mesh and calls it under a
  ``shard_map`` by rows: ``models/transformer.py:_token_view``.)
* **tensor parallelism** (``model`` axis) — attention heads + MLP
  hidden shard Megatron-style through
  :mod:`bigdl_tpu.parallel.tensor_parallel`'s column/row-parallel
  layout, lowered under ``utils.compat.shard_map`` with the paper-
  canonical TWO collectives per block: one psum closing the attention
  output projection, one closing the MLP. The per-layer K/V cache
  shards on its HEAD axis; embeddings, LayerNorms, the LM head, and
  the sampling epilogue stay replicated. See
  ``models/transformer.py`` (``mesh=`` on the step builders).

The subsystem owns mesh construction (:func:`make_mesh`, over the
devices jax has — a host with too few raises; on a CPU box start python
with ``JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8`` in the environment
and everything here is testable on one host), the sharded pool
(:class:`ShardedKVPool` — slot→(shard, row) mapping, balanced
cross-shard allocation, mesh-pinned admission scatter), and the
:class:`ShardedEngine` front end. The stock
:class:`~bigdl_tpu.serving.engine.ServingEngine` swaps the plane in via
its ``mesh=``/``parallelism=`` knobs; admission
(:class:`~bigdl_tpu.serving.admission.AdmissionController`) and the
:class:`~bigdl_tpu.serving.prefix_cache.PrefixCache` are UNCHANGED —
their output rows route to the owning shard through the pool's
mesh-aware scatter.

    from bigdl_tpu.serving.sharded import ShardedEngine

    eng = ShardedEngine(lm, parallelism={"data": 4, "model": 2},
                        n_slots=8)
    rid = eng.submit([3, 7, 2], max_new_tokens=32)
    outs = eng.drain()                   # token-identical to unsharded
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from bigdl_tpu.serving.kv_pool import KVPool

#: Axis names of every mesh this plane builds: requests shard over
#: ``data`` (slot rows), weights over ``model`` (heads / MLP hidden).
DATA_AXIS = "data"
MODEL_AXIS = "model"


def make_mesh(data: int = 1, model: int = 1, devices=None):
    """A ``jax.sharding.Mesh`` of shape ``(data, model)`` with the
    plane's canonical axis names, built from ``devices`` (default: all
    of ``jax.devices()``, first ``data*model`` taken). Raises with the
    CPU-emulation recipe when the host has too few devices."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    if data < 1 or model < 1:
        raise ValueError(f"axis sizes must be >= 1, got data={data} "
                         f"model={model}")
    devs = list(devices) if devices is not None else list(jax.devices())
    need = data * model
    if len(devs) < need:
        raise ValueError(
            f"mesh ({data} data x {model} model) needs {need} devices, "
            f"host has {len(devs)} — to emulate them on a CPU box, start "
            "python with JAX_PLATFORMS=cpu XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need}")
    return Mesh(np.asarray(devs[:need]).reshape(data, model),
                (DATA_AXIS, MODEL_AXIS))


def _axis_size(mesh, name: str) -> int:
    """Size of a mesh axis by name, 1 when the mesh lacks the axis."""
    return int(dict(mesh.shape).get(name, 1))


def named_sharding(mesh, spec):
    """``NamedSharding(mesh, spec)`` with the spec NORMALIZED the way
    jit reports output shardings: axes of size 1 drop to ``None`` and
    trailing ``None`` dims are stripped. Placement must use the same
    spelling the step's outputs will carry — ``P('data')`` over a
    size-1 data axis hashes differently from ``P()``, and one mismatch
    makes every engine step recompile."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sizes = dict(mesh.shape)
    ent = [None if (isinstance(e, str) and sizes.get(e, 1) == 1) else e
           for e in tuple(spec)]
    while ent and ent[-1] is None:
        ent.pop()
    return NamedSharding(mesh, P(*ent))


def _sharding_tree(mesh, specs):
    """Mirror a nested-dict PartitionSpec tree as (normalized)
    NamedShardings (a hand-rolled recursion: PartitionSpec subclasses
    tuple on older jax, so tree_map would flatten INTO the specs)."""
    if isinstance(specs, dict):
        return {k: _sharding_tree(mesh, v) for k, v in specs.items()}
    return named_sharding(mesh, specs)


class ShardedKVPool(KVPool):
    """A :class:`KVPool` whose pooled carry lives sharded on a mesh.

    Slot rows shard over the mesh's data axis in CONTIGUOUS blocks —
    device ``d`` owns slots ``d*rows_per_shard ..
    (d+1)*rows_per_shard - 1`` (:meth:`slot_shard` is the
    slot → (shard, local row) mapping); per-layer K/V additionally
    shard their head axis over the model axis when ``carry_specs`` says
    so. Two behavioral deltas from the base pool:

    * **balanced allocation** — :meth:`alloc` pops a free slot from the
      LEAST-LOADED shard (ties → lowest shard id, LIFO within a shard)
      instead of global LIFO, so admissions spread across devices and
      no shard hoards active rows while others idle (the
      ``serving/shard_imbalance`` metric watches this);
    * **mesh-pinned scatter** — the donated admission scatter compiles
      with explicit output shardings, so every ``write_prefill`` keeps
      the pool's placement bit-stable (a drifting spec spelling would
      silently double-compile the decode program).

    Slot ids, invariants, and every public method are unchanged —
    admission/eviction code cannot tell the pools apart (that is the
    point: the AdmissionController routes rows to the owning shard
    without knowing shards exist).
    """

    def __init__(self, init_carry, n_slots: int, mesh, carry_specs: Dict,
                 data_axis: str = DATA_AXIS,
                 kv_dtype: Optional[str] = None) -> None:
        import jax

        n_shards = _axis_size(mesh, data_axis)
        if n_slots % n_shards:
            raise ValueError(
                f"n_slots {n_slots} not divisible by the data-axis size "
                f"{n_shards} — every shard must own the same number of "
                "decode rows (one program shape)")
        self.mesh = mesh
        self.data_axis = data_axis
        self._shardings = {k: named_sharding(mesh, spec)
                           for k, spec in carry_specs.items()}
        super().__init__(init_carry, n_slots, kv_dtype=kv_dtype)
        self.n_shards = n_shards
        self.rows_per_shard = self.n_slots // n_shards
        # shard the freshly-built carry (init_carry returns host-fresh
        # leaves; one device_put per leaf pins the layout the step's
        # out_specs will preserve forever after)
        self.carry = {k: jax.device_put(v, self._shardings[k])
                      for k, v in self.carry.items()}
        # per-shard LIFO free lists, mirroring the base free list
        self._free_by_shard: List[List[int]] = [
            list(range((s + 1) * self.rows_per_shard - 1,
                       s * self.rows_per_shard - 1, -1))
            for s in range(n_shards)]

    def _make_scatter(self):
        import jax

        return jax.jit(self._scatter_impl, donate_argnums=(0,),
                       out_shardings=self._shardings)

    def _make_free_reset(self):
        import jax

        # pin the reset outputs to the carry's placements — a follower
        # sharding with a drifted spelling would double-compile the one
        # decode program (the PR-4 lesson)
        return jax.jit(self._free_reset_impl, donate_argnums=(0,),
                       out_shardings={k: self._shardings[k]
                                      for k in self._reset_keys})

    # -- draft carry (speculative decoding) --------------------------------

    def _draft_shardings(self, specs):
        if specs is None:
            raise ValueError(
                "a sharded pool needs the draft carry's PartitionSpecs "
                "(ShardPlane.draft_carry_specs) — an unpinned draft "
                "placement would drift from the step outputs and "
                "double-compile")
        return {k: named_sharding(self.mesh, s) for k, s in specs.items()}

    def _place_draft(self, carry, specs):
        import jax

        sh = self._draft_shardings(specs)
        return {k: jax.device_put(v, sh[k]) for k, v in carry.items()}

    def _make_draft_scatter(self, specs):
        import jax

        return jax.jit(self._scatter_impl, donate_argnums=(0,),
                       out_shardings=self._draft_shardings(specs))

    def _make_draft_reset(self, specs):
        import jax

        sh = self._draft_shardings(specs)
        return jax.jit(self._free_reset_impl, donate_argnums=(0,),
                       out_shardings={"pos": sh["pos"]})

    # -- slot → shard routing ---------------------------------------------

    def slot_shard(self, slot: int) -> Tuple[int, int]:
        """(owning shard, row within that shard) for a slot id — the
        contiguous-block layout of the data-axis sharding."""
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} outside 0..{self.n_slots - 1}")
        return slot // self.rows_per_shard, slot % self.rows_per_shard

    def used_per_shard(self) -> List[int]:
        return [self.rows_per_shard - len(f) for f in self._free_by_shard]

    # -- balanced allocator ------------------------------------------------

    def alloc(self) -> Optional[int]:
        """A free slot from the least-loaded shard (None when full)."""
        best, best_used = None, None
        for s, free in enumerate(self._free_by_shard):
            if not free:
                continue
            used = self.rows_per_shard - len(free)
            if best_used is None or used < best_used:
                best, best_used = s, used
        if best is None:
            return None
        slot = self._free_by_shard[best].pop()
        self._free.remove(slot)
        self._in_use.add(slot)
        return slot

    def free(self, slot: int) -> None:
        super().free(slot)
        self._free_by_shard[self.slot_shard(slot)[0]].append(slot)


class ShardPlane:
    """The engine's view of its mesh: axis sizes, row placement, pool
    and step construction. Built by
    :class:`~bigdl_tpu.serving.engine.ServingEngine` when its
    ``mesh=``/``parallelism=`` knob is set; owns nothing stateful
    beyond the mesh itself.

    ``parallelism`` is a ``{"data": N, "model": M}`` dict (either key
    optional) used to build a mesh from the host's devices when no
    explicit ``mesh`` is given. An explicit mesh must carry BOTH of
    this plane's axis names (``data`` and ``model`` — a size-1 axis is
    fine, :func:`make_mesh` always produces both): the step programs'
    partition specs name both axes, so a mesh missing one would only
    fail later, at the first decode step, with an opaque KeyError."""

    def __init__(self, mesh=None, parallelism: Optional[Dict] = None,
                 data_axis: str = DATA_AXIS,
                 model_axis: str = MODEL_AXIS) -> None:
        if mesh is None:
            parallelism = dict(parallelism or {})
            unknown = set(parallelism) - {"data", "model"}
            if unknown:
                raise ValueError(
                    f"unknown parallelism axes {sorted(unknown)} "
                    "(expected 'data' and/or 'model')")
            mesh = make_mesh(data=int(parallelism.get("data", 1)),
                             model=int(parallelism.get("model", 1)))
        missing = [a for a in (data_axis, model_axis)
                   if a not in mesh.axis_names]
        if missing:
            raise ValueError(
                f"mesh axes {mesh.axis_names} lack {missing} — the "
                f"serving plane's partition specs name both "
                f"'{data_axis}' and '{model_axis}' (size 1 is fine; "
                "build the mesh with serving.sharded.make_mesh)")
        self.mesh = mesh
        self.data_axis = data_axis
        self.model_axis = model_axis
        self.data_shards = _axis_size(mesh, data_axis)
        self.model_shards = _axis_size(mesh, model_axis)
        if self.data_shards == 1 and self.model_shards == 1:
            raise ValueError(
                "a 1x1 mesh is the unsharded engine — drop the "
                "mesh/parallelism knob instead")
        from jax.sharding import PartitionSpec as P

        # leading-axis row sharding for tokens/active/knob arrays
        # (normalized: the spec spelling must match the step's output
        # specs or every call double-compiles)
        self.row_sharding = named_sharding(self.mesh, P(data_axis))

    @property
    def tensor_parallel(self) -> bool:
        return self.model_shards > 1

    def place_rows(self, x):
        """Commit a per-slot array (leading slot axis) to the mesh."""
        import jax

        return jax.device_put(x, self.row_sharding)

    def place_params(self, model, params):
        """Commit a serving params tree to the mesh: Megatron-sharded
        over the model axis for tensor-parallel planes, left on the
        default device (GSPMD replicates it) otherwise. ``model`` is
        the architecture the spec tree mirrors; ``params`` the
        (pre-cast) tree to place."""
        import jax

        if not self.tensor_parallel:
            return jax.device_put(params)
        from bigdl_tpu.models.transformer import tp_param_specs

        return jax.device_put(
            params, _sharding_tree(self.mesh,
                                   tp_param_specs(model, self.model_axis)))

    def carry_specs(self, model, sampling: bool = True,
                    kv_quant: bool = False) -> Dict:
        from bigdl_tpu.models.transformer import serving_carry_specs

        return serving_carry_specs(
            model, sampling=sampling, data_axis=self.data_axis,
            model_axis=self.model_axis if self.tensor_parallel else None,
            kv_quant=kv_quant)

    def draft_carry_specs(self, draft_model) -> Dict:
        """PartitionSpec tree for a speculative DRAFT carry: slot rows
        shard over the data axis like the target's, but K/V heads stay
        UNSHARDED even on tensor-parallel meshes — the draft's weights
        are replicated (a model small enough to draft with is small
        enough to replicate), so its cache heads are whole per chip."""
        from bigdl_tpu.models.transformer import serving_carry_specs

        return serving_carry_specs(draft_model, sampling=False,
                                   data_axis=self.data_axis,
                                   model_axis=None)

    def make_pool(self, model, pool_init, n_slots: int,
                  sampling: bool = True, kv_quant: bool = False,
                  kv_dtype: Optional[str] = None) -> ShardedKVPool:
        return ShardedKVPool(pool_init, n_slots, self.mesh,
                             self.carry_specs(model, sampling=sampling,
                                              kv_quant=kv_quant),
                             data_axis=self.data_axis, kv_dtype=kv_dtype)


class ShardedEngine:
    """Convenience front end: a
    :class:`~bigdl_tpu.serving.engine.ServingEngine` with the sharded
    plane on by default — ``parallelism`` defaults to all visible
    devices data-parallel (``{"data": jax.device_count()}``; on a
    single-device host this degrades to the plain unsharded engine).
    Every other knob passes through. Prefer the plain engine's
    ``mesh=``/``parallelism=`` arguments when you already hold an
    engine construction site; this class exists so one import gives a
    whole-fleet engine:

        eng = ShardedEngine(lm, n_slots=8)                 # all devices
        eng = ShardedEngine(lm, parallelism={"data": 2, "model": 4})
    """

    def __new__(cls, model, mesh=None, parallelism=None, **kwargs):
        import jax

        from bigdl_tpu.serving.engine import ServingEngine

        if mesh is None and parallelism is None:
            n = jax.device_count()
            # one visible device = nothing to shard over: degrade to the
            # plain engine rather than erroring about a knob the caller
            # never set (the ShardPlane 1x1 guard targets explicit use)
            parallelism = {"data": n} if n > 1 else None
        return ServingEngine(model, mesh=mesh, parallelism=parallelism,
                             **kwargs)

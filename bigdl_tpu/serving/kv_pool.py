"""Pooled, slot-indexed KV cache with a free-list allocator.

The serving analog of the reference's fixed executor pool (SoCC'19: work
is scheduled onto a FIXED set of executors instead of spawning per-job
state): decode capacity is ``n_slots`` rows of ONE pooled per-layer K/V
cache, allocated/freed per request through a free list, instead of the
per-call private carries ``generate()`` builds. One pool + one compiled
step means admission and eviction never change tensor shapes — the XLA
program is compiled once and reused for the engine's whole lifetime.

The pool's tensors ARE the carry of the model family's decode step
(``serving/family.py``), so the engine hands ``pool.carry`` straight to
the step function and stores the returned carry back. Every leaf has
the slot as its first axis, and the pool treats each by WHAT IT IS
(:func:`leaf_kind`), whatever family built the carry:

* ``pos`` — the row's position counter;
* ``kv`` — ``k{i}`` / ``v{i}``, position-indexed caches ``(n_slots,
  len_i, heads*head_dim)``: admission scatters them, ``free()``
  leaves them (stale rows are masked by ``pos``). A ``kv`` leaf need
  not have a twin: a latent-attention family keeps ONE leaf ``k{i}`` a
  layer, whose leading columns are its values, and no ``v{i}``
  (``models/glm_moe_lite.py``); nothing here pairs the two. ``len_i`` is the
  LAYER's: the family's cache window ``max_len`` for a layer that
  attends over the whole context, less for a sliding-window layer,
  whose leaf is a RING that holds position ``p`` at ``p % len_i`` and
  is valid up to ``min(pos, len_i)`` entries (``models/afmoe.py``).
  Everything here goes by each leaf's own length; the scatter writes
  the columns a prefilled row brings, which may be fewer than the
  leaf's (a bucket shorter than the window);
* ``scale`` — ``k{i}_scale`` / ``v{i}_scale``, the int8 layout's
  per-(slot, head) dequant scales: scattered with their rows, reset to
  zero on ``free()`` (grow-only mid-flight);
* ``lane`` — ``rng`` / ``tok_counts`` / ``prompt_mask``, the sampling
  state :meth:`KVPool.write_sampling` seeds per admission;
* ``state`` — every other per-slot leaf, state that is read WHOLE every
  token whatever the position (a recurrent family's scan state
  ``ssm{i}`` and convolution window ``conv{i}``,
  ``models/falcon_h1.py``): scattered at admission like K/V, and reset
  to zero on ``free()``, because no ``pos`` masks it and a row that
  starts decoding without a prefill must not inherit its predecessor's.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from bigdl_tpu.serving.metrics import span

_FREE_RESET = None

_KV_KEY = re.compile(r"[kv]\d+")
_SCALE_KEY = re.compile(r"[kv]\d+_scale")
_LANE_KEYS = ("rng", "tok_counts", "prompt_mask")


def leaf_kind(key: str) -> str:
    """What a per-slot carry leaf is, by its key: ``"pos"``, ``"kv"``,
    ``"scale"``, ``"lane"`` or ``"state"`` (module docstring)."""
    if key == "pos":
        return "pos"
    if _KV_KEY.fullmatch(key):
        return "kv"
    if _SCALE_KEY.fullmatch(key):
        return "scale"
    return "lane" if key in _LANE_KEYS else "state"


def _shared_free_reset():
    """Lazily-built process-wide jitted free-reset (see
    KVPool._make_free_reset for why it is shared)."""
    global _FREE_RESET
    if _FREE_RESET is None:
        import jax

        _FREE_RESET = jax.jit(KVPool._free_reset_impl,
                              donate_argnums=(0,))
    return _FREE_RESET


class KVPool:
    """Fixed-capacity pooled KV cache: ``n_slots`` independent rows.

    * :meth:`alloc` pops a slot id off the free list (None when full);
    * :meth:`free` zeroes the row's position and returns the slot;
    * :meth:`write_prefill` row-scatters one row of a prefilled carry
      (a ``make_prefill_step`` B=1 carry, or any row of a
      ``make_batch_prefill_step`` batched-admission carry) into a
      slot — the cheap admission path for mid-flight continuous
      batching.

    Invariants (pinned by tests/test_serving.py): a slot is never handed
    out twice without an intervening free (no aliasing), ``free`` of an
    unallocated slot raises, and after every request drains the free
    list holds all ``n_slots`` again (no leaks).

    ``kv_dtype`` (``"fp32"``/``"bf16"``/``"int8"``, None = infer) is
    the declarative storage-format knob: it must match what the carry
    actually stores (``make_batch_decode_step``'s ``kv_quant``/
    ``compute_dtype`` knobs decide that), and mismatches raise at
    construction. An int8 carry brings per-(slot, head) fp32 dequant
    scales (``k{i}_scale``/``v{i}_scale``) that ride the admission
    scatter with their rows and reset to zero on ``free`` (scales are
    grow-only mid-flight — a recycled slot must not inherit its
    previous occupant's range). ``kv_bytes_per_slot`` is the per-slot
    KV footprint in bytes (payload + scales) — the capacity
    denominator behind the serving metrics and the kv_quant bench;
    ``state_bytes_per_slot`` counts the slot's ``state`` leaves (0 for
    a family that keeps none), which a slot holds in full whatever its
    position.
    """

    def __init__(self, init_carry, n_slots: int,
                 kv_dtype: Optional[str] = None,
                 max_len: Optional[int] = None) -> None:
        import jax
        import numpy as np

        from bigdl_tpu.ops.decode_attention import auto_block_l

        if n_slots <= 0:
            raise ValueError(f"n_slots must be positive, got {n_slots}")
        self.n_slots = int(n_slots)
        # one logical shard; the mesh-aware subclass (serving.sharded.
        # ShardedKVPool) overrides these with the slot-axis shard count
        # and per-shard row block
        self.n_shards = 1
        self.rows_per_shard = self.n_slots
        self.carry = init_carry(self.n_slots)
        # k0, k1, ... — NOT k0_scale (the int8 layout's dequant scales)
        self.n_layers = sum(1 for k in self.carry
                            if k[0] == "k" and leaf_kind(k) == "kv")
        # the positions a slot may hold: the family's cache window
        # where the engine hands it over, else the longest K/V leaf
        # (``k0`` may be a ring shorter than the window)
        self.max_len = int(max_len or max(
            v.shape[1] for k, v in self.carry.items()
            if leaf_kind(k) == "kv"))
        self.quantized = "k0_scale" in self.carry
        # the storage-format knob is declarative: the carry (built by
        # make_batch_decode_step's init_carry) is the ground truth, and
        # a mismatched claim here would mean the engine wired its knobs
        # inconsistently — fail loudly at construction, not at serve
        stored = np.dtype(self.carry["k0"].dtype).name
        stored = {"float32": "fp32", "bfloat16": "bf16"}.get(stored, stored)
        if kv_dtype is not None and kv_dtype != stored:
            raise ValueError(
                f"kv_dtype={kv_dtype!r} but the carry stores K/V as "
                f"{stored!r} — build the carry with the matching "
                "make_batch_decode_step(kv_quant=...) knob")
        self.kv_dtype = stored
        # bytes of KV state ONE slot owns (int8 payload + its scales,
        # or the float cache): the capacity denominator the kv_quant
        # bench and serving/kv_bytes_per_slot metric report; and the
        # bytes of its other per-slot state (recurrent families)
        def slot_bytes(*kinds):
            return int(sum(
                v.dtype.itemsize * int(np.prod(v.shape[1:]))
                for k, v in self.carry.items() if leaf_kind(k) in kinds))

        self.kv_bytes_per_slot = slot_bytes("kv", "scale")
        # (leaf length, the decode kernel's block over it) -> bytes a
        # position, summed over the K/V leaves of that length: what a
        # row at ``pos`` really holds of its slot (kv_held_bytes) and
        # what a decoding row has fetched of it (kv_fetched_bytes); one
        # entry where every leaf is ``max_len``
        self._kv_position_bytes: Dict[Tuple[int, int], int] = {}
        for k, v in self.carry.items():
            if leaf_kind(k) == "kv":
                nbytes = v.dtype.itemsize * int(np.prod(v.shape[2:]))
                key = (int(v.shape[1]), auto_block_l(int(v.shape[1]), nbytes))
                self._kv_position_bytes[key] = \
                    self._kv_position_bytes.get(key, 0) + nbytes
        # bytes ONE position holds over all K/V leaves of a slot, as
        # stored (a latent row's lane padding included)
        self.kv_position_bytes = sum(self._kv_position_bytes.values())
        self.state_bytes_per_slot = slot_bytes("state")
        # LIFO free list: the most recently freed row is the most likely
        # to still be resident in cache/HBM
        self._free: List[int] = list(range(self.n_slots - 1, -1, -1))
        self._in_use: set = set()
        # ONE jitted, donated scatter for admissions: copies every
        # layer's full B=1 prefill row into the slot in place. Op-by-op
        # eager updates would allocate 2*n_layers full-pool output
        # buffers per admission (hundreds of MB of HBM traffic at LM
        # scale); donation updates the pool buffers in place, and
        # copying the FULL max_len row (tail zeros included — masked by
        # pos anyway) keeps the program length-independent, so it
        # compiles exactly once per pool. (_make_scatter is the subclass
        # hook: the sharded pool pins the output shardings so scattered
        # carries keep their mesh placement.)
        self._scatter = self._make_scatter()
        # ONE jitted, donated reset for free(): pos plus, on the int8
        # layout, every (slot, head) dequant-scale row, plus every
        # ``state`` leaf's row (module docstring). Op-by-op eager
        # .at[].set would be 1 + 2*n_layers separate device dispatches
        # (each allocating a fresh buffer) on the request-completion hot
        # path; the slot id is a traced scalar so the program compiles
        # once per pool. (_make_free_reset is the subclass hook — the
        # sharded pool pins output shardings, same as the scatter.)
        self._reset_keys = [k for k in self.carry
                            if leaf_kind(k) in ("pos", "scale", "state")]
        self._free_reset = self._make_free_reset()
        # CHUNK-PROGRESS tracking (chunked streaming admission —
        # serving/chunked.py): host-side mirrors of how much of a
        # slot's prompt is resident (`chunk_done`, kept in lockstep
        # with the device `pos` by write_prefill/set_pos) and how much
        # it ultimately needs (`chunk_target`, set by begin_chunks;
        # 0 = no chunk plan). Host ints, so the chunk pump never reads
        # the device back mid-stream. Both RESET with their slot in
        # free() — the same recycled-slot contract the int8 scales
        # follow: a new occupant must never inherit its predecessor's
        # progress (a stale target would make a fresh row look
        # mid-prefill and stall its activation forever).
        self.chunk_done = np.zeros((self.n_slots,), np.int64)
        self.chunk_target = np.zeros((self.n_slots,), np.int64)
        # per-slot ADAPTER id (multi-tenant LoRA — serving/lora.py):
        # a host-side int mirror the engine feeds to the compiled steps
        # as per-row runtime data. 0 = the null adapter (base model).
        # Host ints like the chunk mirrors, and reset with the slot in
        # free() under the same recycled-slot contract — a leaked id
        # would serve the next occupant through the wrong tenant's
        # factors.
        self.adapter_ids = np.zeros((self.n_slots,), np.int32)
        # optional DRAFT carry (speculative decoding): a second,
        # slot-aligned pooled carry for the draft model — see
        # attach_draft()
        self.draft_carry = None

    def _make_scatter(self):
        import jax

        return jax.jit(self._scatter_impl, donate_argnums=(0,))

    def _make_free_reset(self):
        # ONE process-wide jitted wrapper (module cache): pools come and
        # go with engines, and a per-instance jax.jit would re-trace the
        # same-shaped reset for every new engine — inside a timed serve
        # for benches that construct engines per pass. Shapes/dtypes key
        # jit's own cache, so unrelated pool layouts still coexist. (The
        # sharded subclass overrides with a per-instance wrapper — its
        # output shardings are mesh-specific.)
        return _shared_free_reset()

    @staticmethod
    def _free_reset_impl(leaves, slot):
        return {k: v.at[slot].set(0) for k, v in leaves.items()}

    @staticmethod
    def _scatter_impl(carry, prefill_carry, slot, pos, row):
        # layer keys derive from the CARRY (static under trace), so one
        # impl serves both the target pool and an attached draft carry
        # (different layer counts/shapes key jit's own cache)
        from jax import lax

        out = dict(carry)
        for key in carry:
            # K/V rows, the int8 layout's (1, heads) dequant scales (a
            # quantized row is meaningless without them) and every
            # ``state`` leaf land together; the sampling lanes are
            # write_sampling's
            if leaf_kind(key) not in ("kv", "scale", "state"):
                continue
            src = lax.dynamic_slice_in_dim(
                prefill_carry[key], row, 1, axis=0
            ).astype(carry[key].dtype)
            out[key] = lax.dynamic_update_slice(
                carry[key], src, (slot,) + (0,) * (carry[key].ndim - 1))
        out["pos"] = carry["pos"].at[slot].set(pos)
        return out

    # -- allocator ---------------------------------------------------------

    def alloc(self) -> Optional[int]:
        """A free slot id, or None when the pool is saturated."""
        if not self._free:
            return None
        slot = self._free.pop()
        self._in_use.add(slot)
        return slot

    def free(self, slot: int) -> None:
        if slot not in self._in_use:
            raise ValueError(f"slot {slot} is not allocated")
        self._in_use.remove(slot)
        self._free.append(slot)
        # reset the row's position so a recycled slot starts fresh; the
        # stale K/V rows are harmless (masked by pos) and zeroing them
        # would be pure HBM traffic. ``state`` leaves are zeroed: they
        # are read whole from the first token on. On the int8 layout the
        # dequant scales reset too: scales are grow-only in-step, so a recycled
        # slot MUST drop its previous occupant's scale — a stale large
        # scale would quantize the next request's (smaller) values
        # coarsely for its whole lifetime. One donated jitted dispatch
        # covers pos + all scale rows (see _make_free_reset).
        import jax.numpy as jnp

        with span("pool.write"):
            self.carry.update(self._free_reset(
                {k: self.carry[k] for k in self._reset_keys},
                jnp.int32(slot)))
            # chunk-progress fields reset with the slot (recycled-slot
            # contract): a leaked done/target pair would make the next
            # occupant look mid-prefill
            self.chunk_done[slot] = 0
            self.chunk_target[slot] = 0
            self.adapter_ids[slot] = 0
            if self.draft_carry is not None:
                # the draft carry frees WITH its slot: same pos-reset
                # rule (stale draft K/V behind pos are masked, like the
                # target's)
                self.draft_carry.update(self._draft_reset(
                    {"pos": self.draft_carry["pos"]}, jnp.int32(slot)))

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def used_slots(self) -> int:
        return len(self._in_use)

    def occupancy(self) -> float:
        # guard n_slots == 0 rather than divide: the constructor forbids
        # it today, but subclasses/metrics must never turn an empty pool
        # into a ZeroDivisionError mid-serving
        return self.used_slots / self.n_slots if self.n_slots else 0.0

    def kv_held_bytes(self, pos: int) -> int:
        """Bytes of K/V a row at position ``pos`` holds: per leaf
        ``min(pos, len_i)`` positions (a ring never holds more than its
        window)."""
        return sum(min(int(pos), length) * nbytes
                   for (length, _), nbytes in self._kv_position_bytes.items())

    def kv_fetched_bytes(self, pos) -> int:
        """Bytes of K/V the decode program's attention fetches for rows
        decoding at the (inclusive) positions ``pos``: per leaf the
        whole blocks that hold columns ``0..min(pos, len_i - 1)``, by
        the function the kernel lays its grid out from
        (``ops.decode_attention.fetched_blocks``). COMPUTED from shapes
        and host state, not measured: it is what the kernel fetches on
        a TPU at its automatic block (``auto_block_l``: no decode
        program passes another), whatever runs elsewhere."""
        import numpy as np

        from bigdl_tpu.ops.decode_attention import fetched_blocks

        pos = np.asarray(pos, np.int64)
        return int(sum(
            fetched_blocks(pos, True, length, block).sum() * block * nbytes
            for (length, block), nbytes in self._kv_position_bytes.items()))

    def used_per_shard(self) -> List[int]:
        """Allocated-slot count per shard (one logical shard here; the
        mesh-aware subclass reports per-device counts — the imbalance
        signal ServingMetrics surfaces)."""
        return [self.used_slots]

    def __repr__(self) -> str:
        shards = "" if self.n_shards == 1 else f", n_shards={self.n_shards}"
        kv = "" if not self.quantized else f", kv_dtype={self.kv_dtype}"
        return (f"{type(self).__name__}(n_slots={self.n_slots}, "
                f"used={self.used_slots}, free={self.free_slots}"
                f"{shards}{kv})")

    # -- prefill admission -------------------------------------------------

    def write_prefill(self, slot: int, prefill_carry: Dict,
                      prompt_len: int, row: int = 0) -> None:
        """Row-scatter row ``row`` of a prefilled carry into ``slot``:
        per-layer K/V positions ``0..prompt_len-1`` land in the pooled
        row and the slot's ``pos`` becomes ``prompt_len`` — after this
        the slot decodes exactly as if it had been stepped
        ``prompt_len`` times. ``prefill_carry`` may be the old B=1
        per-request carry (``row=0``) or a multi-row batched-admission
        carry (``make_batch_prefill_step`` output — ``row`` picks the
        request's row). Every column the carry's leaves bring is copied
        (the full row for the families whose prefill fills a pool-shaped
        carry; ``min(bucket, len_i)`` columns where the prefill makes
        its fresh rows itself) — the tail beyond ``prompt_len`` is
        invisible behind ``pos`` — via the jitted donated scatter built
        in ``__init__`` (one trace per prefill-carry shape; ``row``
        rides as a traced argument)."""
        import jax.numpy as jnp

        if slot not in self._in_use:
            raise ValueError(f"slot {slot} is not allocated")
        if not 0 < prompt_len <= self.max_len:
            raise ValueError(
                f"prompt_len {prompt_len} outside 1..{self.max_len}")
        if not 0 <= row < prefill_carry["pos"].shape[0]:
            raise ValueError(
                f"row {row} outside the prefill carry's "
                f"{prefill_carry['pos'].shape[0]} rows")
        # the donated scatter's LAUNCH (host time; its device time is
        # the jit__scatter_impl program in the trace)
        with span("pool.write"):
            self.carry = self._scatter(
                self.carry, prefill_carry, jnp.int32(slot),
                jnp.int32(prompt_len), jnp.int32(row))
        # host mirror of the slot's device pos: the chunk pump plans
        # the next chunk from this without a device readback
        self.chunk_done[slot] = prompt_len

    def read_row(self, slot: int) -> Dict:
        """One allocated slot's carry as a B=1 slice, every leaf (K/V
        layers + scales, state leaves, pos, sampling lanes) — the
        carry half of the
        :meth:`row_state` payload a PREEMPTED or handed-off row leaves
        behind. The slices are fresh device arrays (jax
        arrays are immutable), so they survive the slot's ``free()``
        and later scatter BACK via :meth:`restore_row` bitwise — the
        loss-free half of the eviction + readmission contract
        (``ServingEngine._preempt_row``). The dict is also a valid
        :class:`~bigdl_tpu.serving.prefix_cache.PrefixCache` entry (the
        cache stores exactly such B=1 carries), so preempted state can
        be shared with other requests on the same prefix."""
        if slot not in self._in_use:
            raise ValueError(f"slot {slot} is not allocated")
        return self._fresh_rows(self.carry, slot)

    def _fresh_rows(self, carry: Dict, slot: int) -> Dict:
        """B=1 slices of ``carry`` at ``slot`` that are guaranteed
        FRESH buffers. The guarantee matters on an n_slots == 1 pool:
        jax returns the array ITSELF for a full-window slice, so the
        "stash" would alias the live pool buffers and die with the
        next donated scatter/reset — the latent single-slot stash bug
        the unified row_state API exists to close (pinned by
        tests/test_serving_disagg.py)."""
        import jax.numpy as jnp

        rows = {k: v[slot:slot + 1] for k, v in carry.items()}
        if self.n_slots == 1:
            rows = {k: jnp.array(v, copy=True) for k, v in rows.items()}
        return rows

    def set_pos(self, slot: int, pos: int) -> None:
        """Set one slot's position counter (the no-prefill admission path:
        a 1-token prompt starts decoding at pos 0)."""
        if slot not in self._in_use:
            raise ValueError(f"slot {slot} is not allocated")
        self.carry["pos"] = self.carry["pos"].at[slot].set(int(pos))
        self.chunk_done[slot] = int(pos)

    # -- unified row serialization (stash + handoff) -----------------------

    def row_state(self, slot: int) -> Dict:
        """EVERYTHING one allocated slot carries, as the canonical row
        payload (``serving/disagg.py``'s ``ROW_PAYLOAD_KEYS`` schema
        minus the request metadata): the B=1 target-carry slice from
        :meth:`read_row` (K/V layers, int8 dequant scales, ``state``
        leaves, ``pos``, and
        — on sampling carries — the RNG lane, penalty counts, and
        prompt mask), the ``chunk_done``/``chunk_target``/``adapter``
        host mirrors,
        and the attached DRAFT carry's B=1 slice (``None`` without
        one). This is THE row-serialization API: the engine's
        preemption stash, the disaggregated prefill→decode handoff,
        AND the host spill tier (``serving/kv_tier.py`` packs exactly
        this payload through ``pack_payload`` before it leaves HBM —
        the SRV207 codec discipline) all speak it, so a per-slot field
        added to the carry can never again be captured by one path and
        silently dropped by another (the latent-bug class the old
        carry-only stash invited). :meth:`restore_row` is the inverse —
        byte-identical, pinned by tests/test_serving_disagg.py and
        tests/test_serving_tiered.py."""
        payload = {"carry": self.read_row(slot),
                   "chunk_done": int(self.chunk_done[slot]),
                   "chunk_target": int(self.chunk_target[slot]),
                   "adapter": int(self.adapter_ids[slot]),
                   "draft": None}
        if self.draft_carry is not None:
            payload["draft"] = self._fresh_rows(self.draft_carry, slot)
        return payload

    def restore_row(self, slot: int, payload: Dict) -> None:
        """Scatter a :meth:`row_state` payload into an allocated slot,
        byte-identically: K/V + scales + state leaves + ``pos`` through
        the donated admission scatter, sampling lanes/counts/mask by
        direct row
        set (the :meth:`write_sampling` leaves, restored verbatim
        instead of rebuilt), the chunk mirrors from the payload's own
        values, and the draft slice through the draft scatter when both
        sides carry one. Accepts device arrays (in-process stash) and
        the numpy arrays a deserialized transfer payload holds alike —
        and never reads the device back (ASY301): the scatter's ``pos``
        rides as the payload's own traced scalar, so a hot-path restore
        costs dispatches, not syncs. A pos == 0 row (a 1-token prompt
        that never prefilled) scatters harmlessly — its K/V bytes are
        zeros/stale behind pos, like any recycled slot's."""
        import jax.numpy as jnp

        if slot not in self._in_use:
            raise ValueError(f"slot {slot} is not allocated")
        with span("pool.write"):
            carry = payload["carry"]
            # one donated scatter restores K/V + scales and sets pos from
            # the payload's own (traced) value
            self.carry = self._scatter(
                self.carry, carry, jnp.int32(slot),
                jnp.asarray(carry["pos"])[0], jnp.int32(0))
            # sampling lanes ride the payload (write_sampling's leaves):
            # restored verbatim, not rebuilt — the handoff receiver must
            # reproduce the sender's lane state without knowing its seed
            for key in _LANE_KEYS:
                if key in carry and key in self.carry:
                    self.carry[key] = self.carry[key].at[slot].set(
                        jnp.asarray(carry[key])[0])
            # host mirrors from the payload's own values (SRV203 lockstep):
            # a completed prefill hands off done == pos, target == 0 or pos
            self.chunk_done[slot] = int(payload["chunk_done"])
            self.chunk_target[slot] = int(payload["chunk_target"])
            # adapter id rides the payload (absent in pre-adapter payloads
            # → null adapter, today's behavior)
            self.adapter_ids[slot] = int(payload.get("adapter", 0))
            draft = payload.get("draft")
            if draft is not None and self.draft_carry is not None:
                self.draft_carry = self._draft_scatter(
                    self.draft_carry, draft, jnp.int32(slot),
                    jnp.asarray(draft["pos"])[0], jnp.int32(0))

    # -- chunk progress (chunked streaming admission) ----------------------

    def begin_chunks(self, slot: int, done: int, target: int) -> None:
        """Open a chunk plan on an allocated slot: ``done`` prompt
        tokens are already resident (0 for a fresh row, the matched
        length after a prefix-cache head write), ``target`` is the full
        prefill length the row needs before it may decode. The chunk
        pump (``serving/chunked.py``) advances ``chunk_done`` through
        ``write_prefill`` until it reaches ``target``."""
        if slot not in self._in_use:
            raise ValueError(f"slot {slot} is not allocated")
        if not 0 <= done <= target <= self.max_len:
            raise ValueError(
                f"chunk plan done={done}..target={target} outside "
                f"0..{self.max_len}")
        self.chunk_done[slot] = int(done)
        self.chunk_target[slot] = int(target)

    def chunk_remaining(self, slot: int) -> int:
        """Prompt tokens still to stream for a slot's chunk plan
        (0 = complete or no plan)."""
        return int(max(0, self.chunk_target[slot] - self.chunk_done[slot]))

    # -- sampling lanes ----------------------------------------------------

    def write_sampling(self, slot: int, key, prompt_ids,
                       output_ids=()) -> None:
        """Seed one slot's SAMPLING state at admission (requires a
        sampling-enabled carry — ``make_batch_decode_step(...,
        sampling=True)``): the row's RNG lane becomes ``key`` (derived
        from the REQUEST's seed, never from the slot — so a request
        readmitted into a different slot after an eviction continues
        the exact same lane), its generated-token counts are rebuilt
        from ``output_ids`` (empty for a fresh request — zero counts;
        the tokens emitted so far for a preempted/fault-evicted request
        being READMITTED mid-stream, reproducing exactly the counts the
        in-flight row accumulated one draw at a time), and its
        prompt-membership mask is rebuilt from ``prompt_ids`` (1-based;
        feeds the repetition penalty — the ORIGINAL prompt only, never
        the emitted continuation, matching the in-flight state). Stale
        state from the slot's previous occupant is fully overwritten —
        recycled slots leak nothing into the new request's
        distribution."""
        import jax.numpy as jnp
        import numpy as np

        if "rng" not in self.carry:
            raise ValueError(
                "this pool's carry has no sampling state — build it "
                "from make_batch_decode_step(..., sampling=True)")
        if slot not in self._in_use:
            raise ValueError(f"slot {slot} is not allocated")
        # the (V,) host rows and their three row-set launches
        with span("pool.write"):
            V = self.carry["tok_counts"].shape[1]
            mask = np.zeros((V,), bool)
            if len(prompt_ids):
                mask[np.clip(np.asarray(prompt_ids, np.int64) - 1,
                             0, V - 1)] = True
            counts = np.zeros((V,), np.int32)
            if len(output_ids):
                ids, reps = np.unique(
                    np.clip(np.asarray(output_ids, np.int64) - 1, 0, V - 1),
                    return_counts=True)
                counts[ids] = reps
            self.carry["rng"] = self.carry["rng"].at[slot].set(
                jnp.asarray(key, jnp.uint32))
            self.carry["tok_counts"] = self.carry["tok_counts"].at[slot].set(
                jnp.asarray(counts))
            self.carry["prompt_mask"] = self.carry["prompt_mask"].at[slot].set(
                jnp.asarray(mask))

    # -- draft carry (speculative decoding) --------------------------------

    def attach_draft(self, init_carry, specs=None) -> None:
        """Attach a DRAFT model's pooled carry alongside the target K/V
        (``bigdl_tpu.serving.speculative``): slot ``s`` of the draft
        carry always belongs to the same request as slot ``s`` here —
        one allocator, two caches. The draft carry is a plain
        :func:`make_batch_decode_step` carry (no sampling state: the
        draft proposes greedily; the REQUEST's lane lives in the target
        carry) and frees/resets with its slot. ``specs`` is ignored on
        the single-device pool (the sharded subclass uses it to pin the
        draft leaves' mesh placement)."""
        if self.draft_carry is not None:
            raise ValueError("a draft carry is already attached")
        self.draft_carry = self._place_draft(init_carry(self.n_slots),
                                             specs)
        self.draft_max_len = int(self.draft_carry["k0"].shape[1])
        self._draft_reset = self._make_draft_reset(specs)
        # last: SPMD104 reads a donating factory's call-site args as the
        # jitted fn's — keep this the final `specs` read in the method
        self._draft_scatter = self._make_draft_scatter(specs)

    def _place_draft(self, carry, specs):
        return carry

    def _make_draft_scatter(self, specs):
        import jax

        # same impl as the admission scatter — layer keys derive from
        # the carry, so the draft's (different) depth/geometry just
        # retraces
        return jax.jit(self._scatter_impl, donate_argnums=(0,))

    def _make_draft_reset(self, specs):
        return _shared_free_reset()

    def write_draft_prefill(self, slot: int, prefill_carry: Dict,
                            prompt_len: int, row: int = 0) -> None:
        """Row-scatter one row of a DRAFT prefill carry into ``slot`` —
        :meth:`write_prefill`'s twin for the attached draft cache."""
        import jax.numpy as jnp

        if self.draft_carry is None:
            raise ValueError("no draft carry attached (attach_draft)")
        if slot not in self._in_use:
            raise ValueError(f"slot {slot} is not allocated")
        if not 0 < prompt_len <= self.draft_max_len:
            raise ValueError(
                f"prompt_len {prompt_len} outside 1..{self.draft_max_len}")
        self.draft_carry = self._draft_scatter(
            self.draft_carry, prefill_carry, jnp.int32(slot),
            jnp.int32(prompt_len), jnp.int32(row))

    def set_draft_pos(self, slot: int, pos: int) -> None:
        """Set one slot's DRAFT position counter (the no-prefill
        admission path, mirroring :meth:`set_pos`)."""
        if self.draft_carry is None:
            raise ValueError("no draft carry attached (attach_draft)")
        if slot not in self._in_use:
            raise ValueError(f"slot {slot} is not allocated")
        self.draft_carry["pos"] = \
            self.draft_carry["pos"].at[slot].set(int(pos))

"""Ref-counted radix-tree prefix cache over token prefixes.

Production prompt streams repeat: a shared system prompt, a few-shot
preamble, a conversation replayed with one more turn. Prefill cost is
linear in prompt length, so recomputing a shared prefix per request is
pure waste. This cache stores PREFILLED CARRIES (B=1
:func:`bigdl_tpu.models.transformer.make_batch_decode_step` rows, K/V
positions ``0..n-1`` + ``pos = n``) keyed by the 0-based token sequence
that produced them, in a path-compressed radix tree — so a lookup finds
the LONGEST cached prefix of a new prompt in one walk, and the admission
path (``serving/admission.py``) clones that carry (jax arrays are
immutable — a clone is free) and prefills only the suffix via
``make_batch_prefill_step``'s nonzero start offsets. Matches need not
land on a stored boundary: because K/V is causal, a cached LONGER
prompt serves any shorter shared prefix as a zero-copy TRUNCATED hit
(same buffers, ``pos`` clamped — see :meth:`PrefixCache._walk`), so one
cached "system prompt + question" entry accelerates every later prompt
sharing the system prompt.

Lifecycle / invariants (pinned by tests/test_serving_admission.py):

* ``acquire(tokens)`` returns ``(carry, matched_len, lease)`` for the
  longest cached prefix (``(None, 0, None)`` on a miss) and bumps the
  lease node's refcount — a LEASED entry is never evicted;
* ``release(lease)`` drops the refcount (never below zero — a double
  release raises);
* ``insert(tokens, carry)`` stores a carry, splitting radix edges as
  needed; re-inserting an existing prefix just refreshes its LRU slot;
* capacity is counted in ENTRIES (each entry is one full B=1 carry —
  ``2 * n_layers`` arrays of ``(1, max_len, heads*head_dim)`` — so
  entry count, not token count, is what bounds memory). When over
  ``max_entries``, the least-recently-used carry with ``refs == 0`` is
  dropped and carry-less leaf chains are pruned; if every entry is
  leased the cache temporarily overflows rather than evicting live
  state.

The stored carries are shared REFERENCES: callers must treat them as
immutable (every consumer here does — prefill returns fresh carries and
the pool scatter never donates its prefill argument).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple


class _Node:
    """One radix-tree node: ``edge`` tokens hang below ``parent``;
    ``n_tokens`` is the full prefix length from the root through this
    node; ``carry`` (when present) is the prefilled B=1 carry for
    exactly that prefix."""

    __slots__ = ("edge", "parent", "children", "carry", "n_tokens",
                 "refs", "last_used")

    def __init__(self, edge: Tuple[int, ...], parent: Optional["_Node"],
                 n_tokens: int) -> None:
        self.edge = edge
        self.parent = parent
        self.children: Dict[int, "_Node"] = {}
        self.carry = None
        self.n_tokens = n_tokens
        self.refs = 0
        self.last_used = 0


class PrefixCache:
    """Radix-tree cache of prefilled prompt prefixes (module docstring)."""

    def __init__(self, max_entries: int = 16, tier=None) -> None:
        if max_entries <= 0:
            raise ValueError(
                f"max_entries must be positive, got {max_entries}")
        self.max_entries = int(max_entries)
        # optional host spill tier (serving/kv_tier.py): capacity
        # eviction DEMOTES refs==0 carries there instead of deleting,
        # and acquire() PROMOTES the best stored prefix back as an
        # ordinary hit — warm-prefix capacity then scales with the
        # tier's host_budget_bytes, not max_entries of HBM. The
        # engine wires its tier in at construction; settable because
        # the cache may be built before the tier.
        self.tier = tier
        self.root = _Node((), None, 0)
        # the tree is NAMESPACED by adapter id (multi-tenant LoRA —
        # serving/lora.py): K/V prefilled under one tenant's factors is
        # only reusable under the SAME factors, so each adapter id gets
        # its own radix root and lookups never cross tenants. Id 0 (the
        # null adapter) is `self.root` — base-model traffic keeps
        # today's shared namespace, hit rate, and entry layout.
        # Capacity, LRU, and leases stay GLOBAL across namespaces: one
        # budget of cached carries, whoever owns them.
        self._roots: Dict[int, _Node] = {0: self.root}
        self._carry_nodes: set = set()
        self._clock = 0
        self.lookups = 0
        self.hits = 0
        self.hit_tokens = 0
        self.evictions = 0

    # -- tree walk ---------------------------------------------------------

    @staticmethod
    def _common(a: Sequence[int], b: Sequence[int]) -> int:
        n = min(len(a), len(b))
        for i in range(n):
            if a[i] != b[i]:
                return i
        return n

    @staticmethod
    def _subtree_carry(node: _Node) -> Optional[_Node]:
        """Any carry-bearing node in ``node``'s subtree (or None). Every
        carry below ``node`` shares ``node``'s full prefix, so any one
        of them can serve a truncated hit for it."""
        stack = [node]
        while stack:
            n = stack.pop()
            if n.carry is not None:
                return n
            stack.extend(n.children.values())
        return None

    def _walk(self, tokens: Tuple[int, ...], root: _Node):
        """Longest usable cached prefix of ``tokens``: ``(node,
        matched_len)``, where ``matched_len <= node.n_tokens`` — a
        strict inequality means a TRUNCATED hit: the donor carry covers
        a longer prompt, but causal K/V at positions ``0..matched-1``
        depend only on tokens ``0..matched-1``, so the same arrays with
        ``pos`` clamped to ``matched_len`` ARE the prefix's prefill
        state (zero-copy — the stale tail is overwritten/masked by the
        suffix prefill and decode exactly like recycled pool rows)."""
        node, i, best, best_len = root, 0, None, 0
        while i < len(tokens):
            child = node.children.get(tokens[i])
            if child is None:
                break
            m = self._common(child.edge, tokens[i:])
            if m == len(child.edge):
                node = child
                i += m
                if node.carry is not None:
                    best, best_len = node, i
                continue
            # ran out mid-edge after m shared tokens: every carry under
            # child still shares tokens[:i+m]
            if m > 0:
                deep = self._subtree_carry(child)
                if deep is not None:
                    best, best_len = deep, i + m
            break
        # the walk fully matched tokens[:i] but the deepest stored carry
        # is shallower (carry-less interior node — e.g. the shared
        # system prompt after an edge split): any carry under it serves
        # a truncated hit at depth i
        if i > best_len:
            deep = self._subtree_carry(node)
            if deep is not None:
                best, best_len = deep, i
        return best, best_len

    # -- lease surface -----------------------------------------------------

    def acquire(self, tokens: Sequence[int], adapter_id: int = 0):
        """Longest-cached-prefix lookup with a lease: returns ``(carry,
        matched_len, lease)``; the lease pins the entry against eviction
        until :meth:`release`. Miss → ``(None, 0, None)``. The carry may
        be a truncated view of a longer cached prefill (see
        :meth:`_walk`) — callers treat it exactly like an exact hit.
        ``adapter_id`` selects the tenant namespace (0 = null adapter =
        today's shared tree); a lookup only ever sees entries inserted
        under the same id."""
        self.lookups += 1
        tokens = tuple(int(t) for t in tokens)
        root = self._roots.get(int(adapter_id))
        if root is None:
            best, matched = None, 0
        else:
            best, matched = self._walk(tokens, root)
        if self.tier is not None:
            # tier promotion: a demoted prefix sharing MORE of this
            # prompt than HBM serves comes back as a real entry (the
            # fresh insert is eviction-immune for its pass), then the
            # re-walk serves it as an ordinary — possibly truncated —
            # hit. The tier counts the fetch; the hit counts below.
            promo = self.tier.promote_prefix(tokens, matched,
                                             adapter_id=int(adapter_id))
            if promo is not None:
                ptoks, carry = promo
                self.insert(ptoks, carry, adapter_id=int(adapter_id))
                best, matched = self._walk(
                    tokens, self._roots[int(adapter_id)])
        if best is None:
            return None, 0, None
        best.refs += 1
        self._touch(best)
        self.hits += 1
        self.hit_tokens += matched
        carry = best.carry
        if best.n_tokens > matched:
            import jax.numpy as jnp

            # zero-copy truncation: same K/V buffers, clamped pos
            carry = dict(carry)
            carry["pos"] = jnp.full_like(carry["pos"], matched)
        return carry, matched, best

    def release(self, lease) -> None:
        """Drop an :meth:`acquire` lease (no-op for a miss's None)."""
        if lease is None:
            return
        if lease.refs <= 0:
            raise ValueError("release without a matching acquire")
        lease.refs -= 1

    # -- insertion / eviction ----------------------------------------------

    def insert(self, tokens: Sequence[int], carry,
               adapter_id: int = 0) -> None:
        """Store ``carry`` as the prefill state for exactly ``tokens``
        (0-based ids, non-empty), splitting edges as needed, under the
        ``adapter_id`` namespace (0 = null adapter)."""
        tokens = tuple(int(t) for t in tokens)
        if not tokens:
            raise ValueError("cannot cache an empty prefix")
        adapter_id = int(adapter_id)
        root = self._roots.get(adapter_id)
        if root is None:
            root = self._roots[adapter_id] = _Node((), None, 0)
        node, i = root, 0
        while i < len(tokens):
            child = node.children.get(tokens[i])
            if child is None:
                child = _Node(tokens[i:], node, len(tokens))
                node.children[tokens[i]] = child
                node, i = child, len(tokens)
                continue
            m = self._common(child.edge, tokens[i:])
            if m == len(child.edge):
                node, i = child, i + m
                continue
            # split the edge at the divergence point
            mid = _Node(child.edge[:m], node, node.n_tokens + m)
            node.children[tokens[i]] = mid
            child.edge = child.edge[m:]
            child.parent = mid
            mid.children[child.edge[0]] = child
            node, i = mid, i + m
        assert node.n_tokens == len(tokens)
        if node.carry is None:
            self._carry_nodes.add(node)
        node.carry = carry
        self._touch(node)
        self._evict_over_capacity(protect=node)

    def _touch(self, node: _Node) -> None:
        self._clock += 1
        node.last_used = self._clock

    def _evict_over_capacity(self, protect: Optional[_Node] = None) -> None:
        # the freshly inserted node is immune for THIS pass — evicting
        # it would throw away the prefill just paid for; if everything
        # else is leased the cache temporarily overflows instead
        while len(self._carry_nodes) > self.max_entries:
            victims = [n for n in self._carry_nodes
                       if n.refs == 0 and n is not protect]
            if not victims:
                return                 # everything leased: overflow
            victim = min(victims, key=lambda n: n.last_used)
            self._drop(victim)
            self.evictions += 1

    def _path_of(self, node: _Node):
        """The full token path from ``node``'s namespace root plus the
        adapter id owning that root ((tokens, None) for a detached
        node) — what a demotion is keyed by."""
        parts = []
        n = node
        while n.parent is not None:
            parts.append(n.edge)
            n = n.parent
        tokens = tuple(t for e in reversed(parts) for t in e)
        for aid, root in self._roots.items():
            if root is n:
                return tokens, aid
        return tokens, None

    def _drop(self, node: _Node) -> None:
        # only capacity eviction reaches here, and it only ever picks
        # refs==0 victims — so a demoted carry never has a live lease
        if self.tier is not None and node.carry is not None:
            tokens, aid = self._path_of(node)
            if tokens and aid is not None:
                self.tier.demote_prefix(tokens, node.carry,
                                        adapter_id=aid)
        node.carry = None
        self._carry_nodes.discard(node)
        # prune now-useless structure: carry-less leaves up the path
        while (node.parent is not None and node.carry is None
               and not node.children and node.refs == 0):
            parent = node.parent
            del parent.children[node.edge[0]]
            node = parent

    # -- introspection -----------------------------------------------------

    @property
    def entries(self) -> int:
        return len(self._carry_nodes)

    def cached_prefixes(self) -> List[int]:
        """Lengths of every cached prefix (sorted; test/debug surface)."""
        return sorted(n.n_tokens for n in self._carry_nodes)

    def hit_rate(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0

    def stats(self) -> Dict[str, float]:
        return {"entries": float(self.entries),
                "lookups": float(self.lookups), "hits": float(self.hits),
                "hit_tokens": float(self.hit_tokens),
                "evictions": float(self.evictions),
                "hit_rate": self.hit_rate()}

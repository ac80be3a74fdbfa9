"""Expert parallelism: mixture-of-experts layers for training and for
serving.

Two layers live here, and they differ in what they promise:

* :func:`moe_layer` (with :func:`top_k_gating`) is TRAINING's: a softmax
  gate, a CAPACITY per expert and the GShard/Switch dense-dispatch
  formulation. Gating, top-k selection and capacity masking are dense
  einsums over a ``(tokens, experts, capacity)`` one-hot
  dispatch/combine tensor, placement is ``lax.all_to_all`` over the mesh
  axis, and tokens beyond an expert's capacity are DROPPED (they
  contribute zero, Switch Transformer semantics). No reference
  counterpart (SURVEY.md §2.4).
* :func:`routed_experts` (with :func:`route_top_k`) is SERVING's: a
  sigmoid router over ALL experts of the layer, of which this chip
  HOLDS a share; it computes the part of the result its own SwiGLU
  experts give for the tokens routed to them, as one grouped product
  over the tokens sorted by held expert. No capacity, no ``(T, E, C)``
  tensor, and NO token is dropped at any load. On one chip it runs
  without its exchange: what the absent experts would add is left out.

Pure functions usable inside any ``jit`` / ``shard_map``.
"""

from __future__ import annotations

from typing import Callable, Optional


def top_k_gating(logits, k: int, capacity: int):
    """Build dispatch/combine tensors from router logits.

    ``logits``: (T, E). Returns ``(dispatch, combine)`` of shape
    (T, E, C): ``dispatch`` is the 0/1 routing tensor, ``combine`` carries
    the gate probabilities on the same support. Top-k per token, positions
    within each expert assigned in token order, overflow dropped.
    """
    import jax
    import jax.numpy as jnp

    T, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    # top-k expert mask per token, built iteratively (k is small and static)
    masked = probs
    sel = []
    for _ in range(k):
        ix = jnp.argmax(masked, axis=-1)                     # (T,)
        onehot = jax.nn.one_hot(ix, E, dtype=probs.dtype)    # (T, E)
        sel.append(onehot)
        masked = masked * (1.0 - onehot)
    dispatch_e = jnp.zeros_like(probs)
    for onehot in sel:
        dispatch_e = dispatch_e + onehot                      # (T, E) 0/1
    # position of each token within its expert's queue (token order)
    pos = jnp.cumsum(dispatch_e, axis=0) - dispatch_e         # (T, E)
    keep = dispatch_e * (pos < capacity)
    pos_onehot = jax.nn.one_hot(
        pos.astype(jnp.int32), capacity, dtype=probs.dtype)   # (T,E,C)
    dispatch = keep[..., None] * pos_onehot                   # (T, E, C)
    gates = probs * keep
    # renormalize the surviving top-k gates per token (Switch/GShard rule)
    denom = jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    combine = (gates / denom)[..., None] * pos_onehot
    return dispatch, combine


def moe_layer(x, router_w, expert_params, expert_fn: Callable,
              axis_name: str = "expert", top_k: int = 1,
              capacity_factor: float = 1.25,
              capacity: Optional[int] = None):
    """Expert-parallel MoE block, called inside shard_map over ``axis_name``.

    * ``x`` — this chip's token shard ``(T_local, d)``.
    * ``router_w`` — replicated router weights ``(d, E)`` over ALL experts.
    * ``expert_params`` — THIS chip's experts' parameters, each leaf with a
      ``(E_local, ...)`` leading axis (host side: shard the ``(E, ...)``
      stack with ``in_specs=P(axis_name)``).
    * ``expert_fn(params_one_expert, tokens) -> tokens`` — the expert net.

    Returns ``(T_local, d)`` combined outputs for this chip's tokens.
    """
    import jax
    import jax.lax as lax
    import jax.numpy as jnp

    n_dev = lax.psum(1, axis_name)
    T, d = x.shape
    E = router_w.shape[1]
    assert E % n_dev == 0, f"{E} experts over {n_dev} chips"
    e_local = E // n_dev
    if capacity is None:
        capacity = max(1, int(capacity_factor * top_k * T / E))

    logits = jnp.matmul(x, router_w)                          # (T, E)
    dispatch, combine = top_k_gating(logits, top_k, capacity)

    # route: (T,E,C)×(T,d) → (E,C,d), then all_to_all so chip j receives
    # every chip's slabs for ITS experts
    slabs = jnp.einsum("tec,td->ecd", dispatch, x)            # (E, C, d)
    slabs = slabs.reshape(n_dev, e_local, capacity, d)
    slabs = lax.all_to_all(slabs, axis_name, split_axis=0, concat_axis=0,
                           tiled=False)                        # (n_dev, e_loc, C, d)
    # merge the senders' capacity slots: expert e now sees n_dev*C tokens
    slabs = slabs.transpose(1, 0, 2, 3).reshape(e_local, n_dev * capacity, d)

    out = jax.vmap(expert_fn)(expert_params, slabs)           # (e_loc, n_dev*C, d)

    # inverse route
    out = out.reshape(e_local, n_dev, capacity, d).transpose(1, 0, 2, 3)
    out = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                         tiled=False)                          # (n_dev, e_loc, C, d)
    out = out.reshape(E, capacity, d)
    return jnp.einsum("tec,ecd->td", combine, out)            # (T_local, d)


def mlp_expert(params, tokens):
    """Default expert net: GELU MLP. ``params = {"w1": (d, h), "b1": (h,),
    "w2": (h, d), "b2": (d,)}`` (one expert's slice, no leading E axis)."""
    import jax
    import jax.numpy as jnp

    h = jax.nn.gelu(jnp.matmul(tokens, params["w1"]) + params["b1"])
    return jnp.matmul(h, params["w2"]) + params["b2"]


# ------------------------------------------------ serving: a share, no drop


def route_top_k(x, router_w, expert_bias, k: int, *,
                route_norm: bool = True, route_scale: float = 1.0):
    """Token-choice routing over ALL experts. ``x`` (T, d), ``router_w``
    (d, E_all), ``expert_bias`` (E_all,). The scores are sigmoids, computed in
    float32 (the products are accumulated and kept in float32, whatever
    ``x``'s dtype); the selection is the top ``k`` of ``score + bias``,
    the weights are the UNBIASED scores of the selected experts,
    normalised to sum to one (``route_norm``) and scaled. Returns
    ``(sel, weights)``: (T, k) int32 expert ids and float32 weights."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    logits = jnp.einsum("td,de->te", x, router_w,
                        preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits)
    _, sel = lax.top_k(scores + expert_bias.astype(jnp.float32), k)
    weights = jnp.take_along_axis(scores, sel, axis=1)
    if route_norm:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), weights * route_scale


def grouped_experts(x, sel, weights, experts, held: int,
                    block_rows: Optional[int] = None):
    """The part of ``sum_k weights[t, k] * Expert_{sel[t, k]}(x[t])``
    that the HELD experts give. ``experts``: SwiGLU matrices ``{"gate":
    (E_held, d, f), "up": (E_held, d, f), "down": (E_held, f, d)}``,
    the parameters of experts ``held .. held + E_held - 1`` of the
    layer; a selection outside that range (or negative: a token that is
    routed nowhere, such as padding) adds nothing.

    Capacity-free grouped product: the (token, choice) pairs of held
    experts are sorted by expert and laid out in blocks of
    ``block_rows`` rows, each expert's pairs padded to whole blocks, so
    that every block belongs to ONE expert; a loop over the blocks in
    use multiplies each by its expert's matrices (a dynamic slice of the
    stack: an expert no token chose is never read). The layout has room
    for the worst case (every pair on a held expert), so nothing is
    ever dropped; only the blocks in use cost time. Returns ``(y,
    counts)``: (T, d) float32 and the (E_held,) int32 count of pairs
    each held expert received."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from bigdl_tpu.models.decoder_ops import swiglu

    T, d = x.shape
    k = sel.shape[1]
    E = jax.tree_util.tree_leaves(experts)[0].shape[0]
    P = T * k
    if block_rows is None:
        # a decode step's few rows: small blocks, one per expert hit; a
        # prefill wave: blocks large enough to keep the matrix unit busy
        block_rows = 256 if P >= 4096 else 16
    Bm = int(block_rows)
    n_blocks = -(-P // Bm) + E              # worst case, statically
    local = sel.reshape(P) - held
    is_held = (local >= 0) & (local < E)
    key = jnp.where(is_held, local, E)       # not held: sorted last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    key_s = key[order]
    bounds = jnp.searchsorted(key_s, jnp.arange(E + 1), side="left")
    start = bounds[:E].astype(jnp.int32)                 # in sorted order
    counts = (bounds[1:] - bounds[:E]).astype(jnp.int32)
    padded = -(-counts // Bm) * Bm
    p_end = jnp.cumsum(padded)
    p_start = p_end - padded                             # in the layout
    used = p_end[-1] // Bm                               # blocks in use
    block_expert = jnp.minimum(
        jnp.searchsorted(p_end, jnp.arange(n_blocks) * Bm, side="right"),
        E - 1).astype(jnp.int32)
    # layout row -> its token (T, the zero row appended to x, where the
    # row pads its expert's last block)
    e_r = jnp.repeat(block_expert, Bm)
    rank = jnp.arange(n_blocks * Bm) - p_start[e_r]
    row_token = jnp.where(
        (rank >= 0) & (rank < counts[e_r]),
        order[jnp.clip(start[e_r] + rank, 0, P - 1)] // k, T)
    # pair -> its layout row (row 0, under weight 0, where not held)
    e_s = jnp.minimum(key_s, E - 1)
    dest_s = jnp.where(key_s < E,
                       p_start[e_s] + jnp.arange(P) - start[e_s], 0)
    dest = jnp.zeros((P,), jnp.int32).at[order].set(
        dest_s.astype(jnp.int32), unique_indices=True)
    x_ext = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)], axis=0)

    def body(b, out):
        rows = lax.dynamic_slice_in_dim(row_token, b * Bm, Bm)
        one = jax.tree_util.tree_map(
            lambda w: lax.dynamic_index_in_dim(w, block_expert[b], 0,
                                               keepdims=False), experts)
        res = swiglu(x_ext[rows], one).astype(x.dtype)
        return lax.dynamic_update_slice_in_dim(out, res, b * Bm, axis=0)

    out = lax.fori_loop(0, used, body,
                        jnp.zeros((n_blocks * Bm, d), x.dtype))
    w = jnp.where(is_held, weights.reshape(P), 0.0).astype(jnp.float32)
    y = jnp.sum(out[dest].astype(jnp.float32).reshape(T, k, d)
                * w.reshape(T, k, 1), axis=1)
    return y, counts


def routed_experts(x, router, experts, held: int, k: int, *, valid=None,
                   route_norm: bool = True, route_scale: float = 1.0,
                   block_rows: Optional[int] = None):
    """The routed half of a serving MoE layer on a chip that holds a
    share of the experts. ``x`` (T, d); ``router = {"w": (d, E_all),
    "bias": (E_all,)}``; ``experts``: the HELD experts' parameters, each
    leaf ``(E_held, ...)``, experts ``held .. held + E_held - 1``;
    ``valid`` (T,) bool marks the tokens that are routed at all
    (padding is routed nowhere and counted nowhere).

    Routing, its normalisation and its scale are over ALL experts
    (:func:`route_top_k`); the sum runs over the selected experts this
    chip holds (:func:`grouped_experts`). Returns ``(y, counts)``: (T,
    d) float32, and (E_held,) int32 tokens each held expert received."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("moe.route"):
        sel, weights = route_top_k(
            x, router["w"], router["bias"], k, route_norm=route_norm,
            route_scale=route_scale)
        if valid is not None:
            sel = jnp.where(valid[:, None], sel, -1)
    with jax.named_scope("moe.experts"):
        return grouped_experts(x, sel, weights, experts, held,
                               block_rows=block_rows)

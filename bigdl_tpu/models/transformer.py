"""Transformer language model — the long-context flagship family.

No reference counterpart (SURVEY.md §5.7: the reference predates
transformers; its sequence story ends at ``Recurrent``). This family is the
showcase for the framework's TPU-native extensions working together:

* :class:`MultiHeadAttention` — Pallas flash kernels locally, ring/Ulysses
  sequence parallelism across chips (``sequence_parallel=``, ``sp_axis=``);
* :class:`Remat` — gradient checkpointing per block for deep stacks;
* mixed precision (``Optimizer.set_compute_dtype``) and the full
  DP/TP/PP/EP planes of ``bigdl_tpu.parallel`` for scale-out.

Built entirely from existing framework modules — the point is that a
transformer is just another ``Sequential`` here.
"""

from __future__ import annotations

from typing import Optional

from bigdl_tpu.nn.attention import MultiHeadAttention
from bigdl_tpu.nn.containers import Container, Remat, Sequential
from bigdl_tpu.nn.linear import Linear
from bigdl_tpu.nn.module import AbstractModule, TensorModule


class LayerNorm(TensorModule):
    """Per-token layer normalization (transformer-standard; the reference's
    BatchNormalization normalizes over the batch instead)."""

    def __init__(self, hidden_size: int, eps: float = 1e-5) -> None:
        super().__init__()
        self.hidden_size = hidden_size
        self.eps = eps

    def init_params(self, rng):
        import jax.numpy as jnp

        return {"weight": jnp.ones((self.hidden_size,), jnp.float32),
                "bias": jnp.zeros((self.hidden_size,), jnp.float32)}

    def apply(self, params, input, state=None, training=False, rng=None):
        import jax.numpy as jnp

        xf = input.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean((xf - mean) ** 2, axis=-1, keepdims=True)
        out = (xf - mean) / jnp.sqrt(var + self.eps)
        out = out * params["weight"] + params["bias"]
        return out.astype(input.dtype), state


class PositionEmbedding(TensorModule):
    """Learned absolute positions added to token embeddings (module-level so
    the structured serializer can resolve it on load). ``sp_axis`` makes it
    shard-aware: inside a shard_map over that axis each chip holds a
    T_local sequence slice, and positions offset by ``axis_index * T_local``
    so they stay GLOBAL (matching ring attention's causal offsets)."""

    def __init__(self, max_len: int, hidden_size: int,
                 sp_axis: Optional[str] = None) -> None:
        super().__init__()
        self.max_len = max_len
        self.hidden_size = hidden_size
        self.sp_axis = sp_axis

    def init_params(self, rng):
        import jax

        return {"pos": 0.02 * jax.random.normal(
            rng, (self.max_len, self.hidden_size))}

    def apply(self, params, input, state=None, training=False, rng=None):
        T = input.shape[1]
        if self.sp_axis is None:
            return input + params["pos"][:T], state
        import jax.lax as lax

        n_shards = lax.psum(1, self.sp_axis)  # static axis size
        if n_shards * T > self.max_len:
            raise ValueError(
                f"global sequence {n_shards * T} exceeds max_len "
                f"{self.max_len} (dynamic_slice would silently clamp)")
        start = lax.axis_index(self.sp_axis) * T
        pos = lax.dynamic_slice_in_dim(params["pos"], start, T)
        return input + pos, state


class TransformerBlock(Container):
    """Pre-norm block: x + MHA(LN(x)); x + MLP(LN(x)). A ``Container`` so
    the child-key/init plumbing is the tested shared scheme."""

    def __init__(self, hidden_size: int, n_heads: int, mlp_ratio: int = 4,
                 causal: bool = True, sequence_parallel: Optional[str] = None,
                 sp_axis: str = "seq", use_flash: str = "auto",
                 flash_block: Optional[int] = None) -> None:
        super().__init__()
        self.ln1 = LayerNorm(hidden_size)
        self.attn = MultiHeadAttention(
            hidden_size, n_heads, causal=causal,
            sequence_parallel=sequence_parallel, sp_axis=sp_axis,
            use_flash=use_flash, flash_block=flash_block)
        self.ln2 = LayerNorm(hidden_size)
        self.fc1 = Linear(hidden_size, mlp_ratio * hidden_size)
        self.fc2 = Linear(mlp_ratio * hidden_size, hidden_size)
        for m in (self.ln1, self.attn, self.ln2, self.fc1, self.fc2):
            self.add(m)

    def apply(self, params, input, state=None, training=False, rng=None):
        import jax

        def run(i, x, r=None):
            m = self.modules[i]
            out, _ = m.apply(params[self._child_key(i)], x, {},
                             training=training, rng=r)
            return out

        x = input + run(1, run(0, input), rng)        # attn(ln1(x))
        h = jax.nn.gelu(run(3, run(2, x)))            # fc1(ln2(x))
        return x + run(4, h), state


class ScanBlocks(Container):
    """``n_layers`` copies of one :class:`TransformerBlock` applied via
    ``lax.scan`` over a stacked-params pytree (every leaf gains a leading
    ``(n_layers,)`` axis).

    The alternative lowering to ``n_layers`` unrolled blocks: ONE compiled
    block program is iterated instead of ``n_layers`` inlined copies, so
    compile time is O(1) in depth and the weight working set cycles
    through the same HBM region each iteration. Step-time impact at LM
    scale is measured in benchmarks/llm_mfu_bench.py (``--layer_scan``) —
    scan forbids cross-layer fusion, so this trades peak step time for
    compile time; see PERF_ANALYSIS_r5.md for the numbers.

    Holds exactly one child (the template block); ``init_params`` stacks
    per-layer inits so each layer starts at a DIFFERENT draw, exactly like
    the unrolled construction."""

    def __init__(self, block: TransformerBlock, n_layers: int) -> None:
        super().__init__()
        if n_layers <= 0:
            raise ValueError(f"n_layers must be positive, got {n_layers}")
        self.n_layers = int(n_layers)
        self.add(block)

    def init_params(self, rng):
        import jax

        block = self.modules[0]
        keys = jax.random.split(rng, self.n_layers)
        per_layer = [block.init_params(k) for k in keys]
        stacked = jax.tree_util.tree_map(
            lambda *leaves: jax.numpy.stack(leaves), *per_layer)
        return {self._child_key(0): stacked}

    def unstacked_params(self, params):
        """Per-layer list view of the stacked params (decode-step /
        export interop — the inverse of init_params' stacking)."""
        import jax

        stacked = params[self._child_key(0)]
        return [jax.tree_util.tree_map(lambda a: a[i], stacked)
                for i in range(self.n_layers)]

    def apply(self, params, input, state=None, training=False, rng=None):
        from jax import lax

        block = self.modules[0]
        stacked = params[self._child_key(0)]

        def body(x, layer_params):
            out, _ = block.apply(layer_params, x, {}, training=training,
                                 rng=None)
            return out, None

        out, _ = lax.scan(body, input, stacked)
        return out, state


def TransformerLM(vocab_size: int, hidden_size: int = 256, n_heads: int = 8,
                  n_layers: int = 4, max_len: int = 1024,
                  mlp_ratio: int = 4, causal: bool = True,
                  remat: bool = False,
                  sequence_parallel: Optional[str] = None,
                  sp_axis: str = "seq",
                  output: str = "logprobs",
                  embed_grad_matmul: bool = False,
                  use_flash: str = "auto",
                  flash_block: Optional[int] = None,
                  layer_scan: bool = False) -> Sequential:
    """GPT-style decoder LM over 1-based token ids ``(B, T)`` →
    per-position log-probs ``(B, T, vocab)``.

    ``remat=True`` checkpoints each block (long-context memory);
    ``sequence_parallel="ring"|"ulysses"`` shards the sequence axis across
    the ``sp_axis`` mesh dimension inside a ``shard_map``.

    ``output="logits"`` drops the final LogSoftMax — pair it with
    :class:`bigdl_tpu.nn.criterion_more.MaskedSoftmaxCECriterion`, which
    fuses the softmax into the loss instead of materializing the
    ``(B, T, vocab)`` log-prob tensor (identical math, gigabytes less HBM
    traffic at LM scale — see benchmarks/llm_mfu_bench.py).

    ``embed_grad_matmul`` routes the token-embedding gradient through a
    one-hot MXU matmul instead of the scatter-add lowering — measured
    slightly SLOWER at GPT-2-small scale on v5e (llm_mfu_bench), so off
    by default; kept as a knob for scatter-bound profiles.

    ``use_flash`` routes through every block to the attention layers'
    constructors (so their own validation applies — e.g. striped_ring
    refuses ``"never"``). ``"auto"`` (default) = flash on TPU at every
    length: IN-MODEL, flash wins even at T=2048 (152.4 vs 261.7 ms/step
    on the 137M config — the dense path's T×T score/softmax
    materialization is pure HBM traffic the rest of the step is already
    starved by), although the STANDALONE kernel microbench
    (flash_bench.py) only breaks even near 8k. Measured in
    llm_mfu_bench.py; ``"never"`` forces the dense path.

    ``flash_block`` overrides the flash kernel's VMEM tile length
    (multiple of 128; None = auto, measured optimal — the in-model sweep
    lives in llm_mfu_bench.py ``--sweep_block``).

    ``layer_scan=True`` lowers the block stack as ONE ``lax.scan`` over
    stacked per-layer params (:class:`ScanBlocks`) instead of
    ``n_layers`` unrolled copies — O(1) compile time in depth; step-time
    tradeoff measured in PERF_ANALYSIS_r5.md.
    """
    if output not in ("logprobs", "logits"):
        raise ValueError(f"unknown output {output!r}")
    if use_flash not in ("auto", "always", "never"):
        raise ValueError(f"unknown use_flash {use_flash!r}")
    from bigdl_tpu.nn.activations import LogSoftMax
    from bigdl_tpu.nn.misc import LookupTable

    model = Sequential()
    model.add(LookupTable(vocab_size, hidden_size,
                          grad_via_matmul=embed_grad_matmul))
    model.add(PositionEmbedding(
        max_len, hidden_size,
        sp_axis=sp_axis if sequence_parallel else None))
    def make_block():
        return TransformerBlock(hidden_size, n_heads, mlp_ratio, causal,
                                sequence_parallel, sp_axis,
                                use_flash=use_flash,
                                flash_block=flash_block)

    if layer_scan:
        block = make_block()
        model.add(ScanBlocks(Remat(block) if remat else block, n_layers))
    else:
        for _ in range(n_layers):
            block = make_block()
            model.add(Remat(block) if remat else block)
    model.add(LayerNorm(hidden_size))
    model.add(Linear(hidden_size, vocab_size))
    if output == "logprobs":
        model.add(LogSoftMax())
    return model


def train_main(argv=None):
    """Train a small TransformerLM on a synthetic (or ``-f`` text) corpus —
    mirrors the rnn/PTB main but on the transformer family."""
    import numpy as np

    from bigdl_tpu.dataset.sample import Sample
    from bigdl_tpu.models.utils import run_training, train_parser
    from bigdl_tpu.nn.criterion import ClassNLLCriterion, TimeDistributedCriterion
    from bigdl_tpu.optim.optim_method import Adam

    p = train_parser("Transformer language model", batch_size=16,
                     learning_rate=3e-3, max_epoch=2)
    p.add_argument("--vocab", type=int, default=100)
    p.add_argument("--seqLen", type=int, default=32)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--remat", action="store_true")
    args = p.parse_args(argv)

    rng = np.random.default_rng(0)
    samples = []
    if args.folder:
        from bigdl_tpu.dataset.text import (
            Dictionary, SequenceWindower, simple_tokenize,
        )

        with open(args.folder) as f:
            tokens = simple_tokenize(f.read())
        d = Dictionary([tokens])
        vocab = d.vocab_size()
        ids = [d.get_index(t) + 1 for t in tokens]
        for ls in SequenceWindower(args.seqLen)(iter([ids])):
            samples.append(Sample(np.asarray(ls.data, np.float32),
                                  np.asarray(ls.labels, np.float32)))
        if not samples:
            raise ValueError(f"{args.folder}: corpus shorter than --seqLen")
    else:
        vocab = args.vocab
        for _ in range(args.synthetic):
            toks = [int(rng.integers(1, vocab + 1))]
            for _ in range(args.seqLen):
                toks.append(1 + (toks[-1] + int(rng.integers(0, 3))) % vocab)
            arr = np.asarray(toks, np.float32)
            samples.append(Sample(arr[:-1], arr[1:]))

    model = TransformerLM(vocab, hidden_size=args.hidden, n_heads=args.heads,
                          n_layers=args.layers, max_len=args.seqLen,
                          remat=args.remat)
    crit = TimeDistributedCriterion(ClassNLLCriterion())
    return run_training(model, samples, crit, args,
                        optim_method=Adam(learning_rate=args.learningRate))


if __name__ == "__main__":
    train_main()


def _cast_keep_scales(tree, compute_dtype):
    """Cast float leaves to the serving dtype; quantized ``w_scale``
    leaves stay fp32 so the dequant multiply keeps full scale precision
    (int8 ``weight_q`` is not floating and passes through untouched).
    THE one copy of the serving-cast rule — used by both
    :func:`serving_params` and :func:`make_decode_step`."""
    if compute_dtype is None:
        return tree
    from bigdl_tpu.optim.train_step import cast_floats

    if isinstance(tree, dict):
        return {k: (v if k == "w_scale"
                    else _cast_keep_scales(v, compute_dtype))
                for k, v in tree.items()}
    return cast_floats(tree, compute_dtype)


def serving_params(model: Sequential, compute_dtype=None):
    """The model's params pre-cast for serving (floats to
    ``compute_dtype``, quantized ``w_scale`` leaves kept fp32) — put this
    on device once and pass it to the decode step as the runtime params
    argument, so weights are resident buffers in the serving dtype rather
    than program constants."""
    model._ensure_params()
    return _cast_keep_scales(model.params, compute_dtype)


def _decode_head_offset(model: Sequential) -> int:
    """1 when the model carries a trailing LogSoftMax (the decode/prefill
    steps apply log_softmax themselves either way), else 0."""
    from bigdl_tpu.nn.activations import LogSoftMax

    return 1 if isinstance(model.modules[-1], LogSoftMax) else 0


def _resolve_decode_views(model: Sequential, off: int, Pt):
    """Navigate a params tree into the views the decode/prefill steps
    read — runs at build time on the captured weights AND in-trace on a
    runtime params argument (same key navigation either way). Returns
    ``(lookup_w, pos_w, [(block_module, block_params)], lnf_p, lin_p)``;
    layer_scan (ScanBlocks) stacks unstack into per-layer views
    (tree_map slices, valid in-trace too)."""
    mods = model.modules
    blocks = []
    for i, m in enumerate(mods):
        inner, bp = m, Pt[model._child_key(i)]
        if isinstance(m, Remat):
            inner, bp = m.modules[0], bp[m._child_key(0)]
        if isinstance(inner, ScanBlocks):
            tmpl = inner.modules[0]
            for lp in inner.unstacked_params(bp):
                t2, p2 = tmpl, lp
                if isinstance(t2, Remat):
                    t2, p2 = t2.modules[0], p2[t2._child_key(0)]
                blocks.append((t2, p2))
            continue
        if isinstance(inner, TransformerBlock):
            blocks.append((inner, bp))
    return (Pt[model._child_key(0)]["weight"],
            Pt[model._child_key(1)]["pos"],
            blocks,
            Pt[model._child_key(len(mods) - 2 - off)],
            Pt[model._child_key(len(mods) - 1 - off)])


def _tree_has_key(tree, key: str) -> bool:
    """True if any nested dict in ``tree`` carries ``key`` (used to
    refuse quantized weight layouts on paths that cannot shard them)."""
    if isinstance(tree, dict):
        return key in tree or any(_tree_has_key(v, key)
                                  for v in tree.values())
    return False


def tp_param_specs(model: Sequential, model_axis: str = "model"):
    """``PartitionSpec`` tree mirroring ``model.params`` for the
    Megatron layout the serving steps shard over ``model_axis``:
    attention QKV + MLP fc1 column-parallel (output rows — head-major
    for QKV, so ``n_heads % tp == 0`` splits whole heads), attention
    output + MLP fc2 row-parallel (input columns, bias replicated and
    added once post-psum), everything else (embeddings, LayerNorms, LM
    head) replicated. Feed it to ``shard_map`` ``in_specs`` or
    ``jax.device_put`` — shard_map hands each chip exactly the slice
    :mod:`bigdl_tpu.parallel.tensor_parallel` expects."""
    import jax
    from jax.sharding import PartitionSpec as P

    model._ensure_params()
    if _tree_has_key(model.params, "weight_q"):
        raise NotImplementedError(
            "tensor-parallel serving does not shard quantized "
            "(weight_q/w_scale) layouts yet — serve the float model or "
            "drop the model-axis sharding")
    specs = jax.tree_util.tree_map(lambda _: P(), model.params)
    for i, m in enumerate(model.modules):
        inner, bp = m, specs[model._child_key(i)]
        if isinstance(inner, Remat):
            inner, bp = inner.modules[0], bp[inner._child_key(0)]
        if isinstance(inner, ScanBlocks):
            raise NotImplementedError(
                "tensor-parallel serving over layer_scan stacks is not "
                "wired up (stacked leaves need a leading layer dim in "
                "every spec) — build the model with layer_scan=False")
        if not isinstance(inner, TransformerBlock):
            continue
        def put(p, weight_spec, bias_spec):
            # spec trees must mirror the params STRUCTURE exactly — a
            # bias spec for a bias-free Linear would desync shard_map's
            # in_specs tree
            p["weight"] = weight_spec
            if "bias" in p:
                p["bias"] = bias_spec
        ap = bp[inner._child_key(1)]
        for wname in ("wq", "wk", "wv"):
            put(ap[wname], P(model_axis, None), P(model_axis))
        put(ap["wo"], P(None, model_axis), P())
        put(bp[inner._child_key(3)], P(model_axis, None), P(model_axis))
        put(bp[inner._child_key(4)], P(None, model_axis), P())
    return specs


def serving_carry_specs(model: Sequential, sampling: bool = False,
                        data_axis: str = "data",
                        model_axis: Optional[str] = None,
                        kv_quant: bool = False):
    """``PartitionSpec`` tree for a :func:`make_batch_decode_step` carry:
    every leaf's slot axis over ``data_axis``, and (when ``model_axis``
    is given) the per-layer K/V lane axis over ``model_axis`` — the
    stored ``(N, max_len, heads*hd)`` array is head-major in its last
    axis, so each model chip owns ``heads_l*hd`` contiguous lanes: its
    own whole heads. Specs
    deliberately carry NO trailing ``None`` dims — ``P("data")`` and
    ``P("data", None, ...)`` are different specs to jit's cache,
    and mixing the two spellings between placement and step output would
    double-compile the one serving program. ``kv_quant`` adds the int8
    path's ``(N, heads)`` dequant-scale leaves — their head axis shards
    over ``model_axis`` alongside the heads they scale. ``data_axis``
    None replicates the rows: the carry of the batched prefill, whose
    sampling leaves (``sampling``) ride through untouched but must
    still be named."""
    from jax.sharding import PartitionSpec as P

    row = P() if data_axis is None else P(data_axis)
    specs = {"pos": row}
    kv = row if model_axis is None else P(data_axis, None, model_axis)
    ks = row if model_axis is None else P(data_axis, model_axis)
    for i in range(_serving_meta(model, None).n_layers):
        specs[f"k{i}"] = kv
        specs[f"v{i}"] = kv
        if kv_quant:
            specs[f"k{i}_scale"] = ks
            specs[f"v{i}_scale"] = ks
    if sampling:
        specs["rng"] = row
        specs["tok_counts"] = row
        specs["prompt_mask"] = row
    return specs


#: The six adapted projections of one transformer block, in block order —
#: the layout contract between a model and a serving
#: :class:`~bigdl_tpu.serving.lora.AdapterBank` (bank keys are
#: ``f"{site}{layer}_a"`` / ``f"{site}{layer}_b"``).
ADAPTER_SITES = ("wq", "wk", "wv", "wo", "fc1", "fc2")


def adapter_site_shapes(model: Sequential):
    """Per-layer ``{site: (out_dim, in_dim)}`` weight shapes for the six
    adapted projections — what a serving AdapterBank sizes its pooled
    low-rank factors against. Quantized (``weight_q``) layouts are
    refused: the adapter delta maths against the float weight shapes,
    and the serving TP plane cannot shard quantized weights anyway."""
    model._ensure_params()
    if _tree_has_key(model.params, "weight_q"):
        raise NotImplementedError(
            "LoRA adapter serving over quantized (weight_q/w_scale) "
            "layouts is not wired up — serve the float model")
    off = _decode_head_offset(model)
    _, _, blocks, _, _ = _resolve_decode_views(model, off, model.params)
    shapes = []
    for blk, bp in blocks:
        ap = bp[blk._child_key(1)]
        layer = {name: tuple(ap[name]["weight"].shape)
                 for name in ("wq", "wk", "wv", "wo")}
        layer["fc1"] = tuple(bp[blk._child_key(3)]["weight"].shape)
        layer["fc2"] = tuple(bp[blk._child_key(4)]["weight"].shape)
        shapes.append(layer)
    return shapes


def adapter_bank_specs(model: Sequential, model_axis: str = "model"):
    """``PartitionSpec`` dict mirroring an AdapterBank's device arrays
    for the Megatron serving layout (:func:`tp_param_specs`'s sibling):
    column-parallel sites (wq/wk/wv/fc1) shard B's OUT axis over
    ``model_axis`` with A replicated — the delta lands directly on the
    chip's head/hidden slice, zero communication; row-parallel sites
    (wo/fc2) shard A's IN axis with B replicated — each chip's partial
    delta folds into the block's one closing psum
    (``row_parallel_linear(partial_add=...)``). The adapter-slot axis is
    always replicated: the bank is tiny next to the weights and every
    chip must gather any row's factors."""
    from jax.sharding import PartitionSpec as P

    specs = {}
    for i in range(_serving_meta(model, None).n_layers):
        for name in ("wq", "wk", "wv", "fc1"):
            specs[f"{name}{i}_a"] = P()
            specs[f"{name}{i}_b"] = P(None, model_axis)
        for name in ("wo", "fc2"):
            specs[f"{name}{i}_a"] = P(None, None, model_axis)
            specs[f"{name}{i}_b"] = P()
    return specs


def _adapter_delta(bank, site: str, ids, h, scale):
    """Per-row pooled-LoRA delta for one adapted projection: gather the
    rows' (A, B) factor pairs from the bank by adapter id and compute
    ``scale * (h @ A_r^T) @ B_r^T`` with fp32 accumulation. Bank slot 0
    is the permanently all-zeros NULL adapter, so base-model rows
    contribute an exact 0.0 and mixed base/tenant traffic stays one
    compiled program (adding 0.0 is the fp identity up to -0.0 → +0.0).
    Returns the raw fp32 accumulator — call sites round once."""
    import jax.numpy as jnp

    a = jnp.take(bank[site + "_a"], ids, axis=0)   # (N, r, in[/tp])
    b = jnp.take(bank[site + "_b"], ids, axis=0)   # (N, out[/tp], r)
    if h.ndim == 2:                                # decode: (N, in)
        z = jnp.einsum("ni,nri->nr", h, a,
                       preferred_element_type=jnp.float32)
        d = jnp.einsum("nr,nor->no", z, b,
                       preferred_element_type=jnp.float32)
    else:                                          # chunk: (N, S, in)
        z = jnp.einsum("nsi,nri->nsr", h, a,
                       preferred_element_type=jnp.float32)
        d = jnp.einsum("nsr,nor->nso", z, b,
                       preferred_element_type=jnp.float32)
    return d * jnp.float32(scale)


# Over-provision a growing scale by this factor. A requantization
# (round(q * s_old / s_new) over the whole stored row) costs up to half
# a quantum of FRESH rounding error each time it runs, and without
# headroom a stationary K/V stream grows its running max ~log(n) times
# over a rollout — stored values accumulate several quanta of drift.
# With headroom, one growth jumps PAST the running max, so follow-up
# maxima land inside the provisioned range and requants become rare
# (~1 per 1.25x growth of the true max); the price is that values use
# 127/1.25 ~ 101 int8 levels instead of 127 (error 0.39% -> 0.49% of
# amax). Net on the serving parity scan: flipped-argmax rollouts drop,
# and decode steps skip most requant work.
_KV_SCALE_HEADROOM = 1.25


def _kv_quant_merge(qc, s_old, amax_new):
    """Grow-only per-(row, head) scale merge for the int8 KV cache —
    THE one copy of the quantized-write rule (decode step, batched
    prefill, and per-request prefill all route through here).

    ``qc``: stored int8 cache ``(R, L, H*D)``; ``s_old``: current
    ``(R, H)`` fp32 scales; ``amax_new``: ``(R, H)`` max |new values|
    about to be written (0 for rows that write nothing — their scale
    and stored values pass through BITWISE: their scale does not grow,
    so the ratio is exactly 1.0 and ``round(q * 1.0)`` is the identity
    on int8 values).

    Returns ``(requantized qc, s_new, s_safe)``: when ``amax_new / 127``
    exceeds the stored scale, the scale jumps to ``_KV_SCALE_HEADROOM``
    times that (see the constant's comment — headroom makes growth
    rare), and already-stored values are requantized to it
    (``round(q * s_old / s_new)`` — one extra rounding, bounded by half
    a quantum of the NEW scale; scales only ever grow, so the ratio is
    ≤ 1 and the result stays in int8 range). ``s_safe`` substitutes 1.0
    for still-zero scales so dividing by it is always defined."""
    import jax.numpy as jnp

    s_cand = amax_new / 127.0
    s_new = jnp.where(s_cand > s_old, s_cand * _KV_SCALE_HEADROOM, s_old)
    s_safe = jnp.where(s_new > 0, s_new, 1.0)
    ratio = jnp.where(s_new > 0, s_old / s_safe, 1.0)
    R, L, H = qc.shape[0], qc.shape[1], s_old.shape[1]
    qc2 = jnp.round(qc.reshape(R, L, H, -1).astype(jnp.float32)
                    * ratio[:, None, :, None]
                    ).astype(jnp.int8).reshape(qc.shape)
    return qc2, s_new, s_safe


def _kv_quant_merge_step(kc, vc, ks_old, vs_old, k_amax, v_amax):
    """Decode-step spelling of the grow-only merge: the full-cache
    requantization is a read-modify-write over every stored K/V byte,
    which would triple the decode step's HBM traffic if it ran
    unconditionally — the exact traffic the int8 cache exists to halve.
    So it runs under ONE ``lax.cond`` per layer: on the common
    no-growth step (headroom makes growth rare — see
    ``_KV_SCALE_HEADROOM``) the cond's identity branch passes the
    caches through and the step touches no cache bytes beyond the
    attention read and the one written column. Numerics are identical
    to the unconditional merge: non-growing (row, head) entries have
    ratio exactly 1.0 and requantize bitwise, so skipping them is
    exact."""
    import jax.numpy as jnp
    from jax import lax

    grew = (jnp.any(k_amax / 127.0 > ks_old) |
            jnp.any(v_amax / 127.0 > vs_old))

    def _grow(args):
        kc, vc, ks_old, vs_old = args
        kc2, ks, _ = _kv_quant_merge(kc, ks_old, k_amax)
        vc2, vs, _ = _kv_quant_merge(vc, vs_old, v_amax)
        return kc2, vc2, ks, vs

    kc, vc, ks, vs = lax.cond(grew, _grow, lambda args: args,
                              (kc, vc, ks_old, vs_old))
    ks_safe = jnp.where(ks > 0, ks, 1.0)
    vs_safe = jnp.where(vs > 0, vs, 1.0)
    return kc, vc, ks, vs, ks_safe, vs_safe


def _kv_quantize(x32, s_safe):
    """fp32 values → int8 at the given (broadcastable) safe scale."""
    import jax.numpy as jnp

    return jnp.clip(jnp.round(x32 / s_safe), -127, 127).astype(jnp.int8)


def _serving_proj(p, x):
    """Linear projection for the serving steps: plain {weight,bias}
    params or a QuantizedLinear weight-only layout (int8 weights convert
    inside the dot's fusion, fp32 accumulate, per-channel scale)."""
    import jax.numpy as jnp
    from jax import lax

    if "weight_q" in p:
        acc = lax.dot_general(
            x.astype(jnp.bfloat16),
            p["weight_q"].astype(jnp.bfloat16),
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        out = (acc * p["w_scale"][:, 0]).astype(x.dtype)
        return out + p["bias"].astype(x.dtype) if "bias" in p else out
    return jnp.matmul(x, p["weight"].T) + p["bias"]


def _tp_row_proj(p, x, axis_name: str, delta32=None):
    """Row-parallel serving projection: this chip's partial product is
    completed by the block's one closing psum; the bias (replicated)
    is added once, post-psum (``parallel.tensor_parallel``'s layout).
    Partials and the psum accumulate fp32 and round to the serving
    dtype ONCE — matching the unsharded matmul's single rounding, so
    bf16 TP serving stays token-aligned with the single-device engine
    instead of drifting an ulp per psum addend. ``delta32``: an fp32
    per-chip LoRA partial delta folded into the SAME psum (the adapter
    path keeps the two-collectives-per-block budget; None = no-op)."""
    import jax.numpy as jnp

    from bigdl_tpu.parallel.tensor_parallel import row_parallel_linear

    return row_parallel_linear(x, p["weight"], p.get("bias"), axis_name,
                               accum_dtype=jnp.float32,
                               partial_add=delta32)


def _proj_fns(adapter=None, adapter_ids=None, bank=None, mesh=None,
              model_axis=None):
    """``(proj, row_proj)`` for one program invocation, both called as
    ``f(p, h, site)``. ``proj`` is the serving projection (under a mesh
    the params are per-chip column-parallel slices, head-major rows, so
    the same call IS the column-parallel half — zero communication)
    plus, with an ``adapter``, the rows' LoRA delta. ``row_proj`` closes
    the attention (``wo``) and the MLP (``fc2``): the same function
    without a mesh; under one, :func:`_tp_row_proj` — the block's two
    collectives — with the adapter's fp32 partial delta inside its
    psum."""
    if adapter is None:
        def delta(h, site):
            return None

        def proj(p, h, site):
            return _serving_proj(p, h)
    else:
        def delta(h, site):
            return _adapter_delta(bank, site, adapter_ids, h, adapter.scale)

        def proj(p, h, site):
            y = _serving_proj(p, h)
            return y + delta(h, site).astype(y.dtype)

    if mesh is None:
        return proj, proj

    def row_proj(p, h, site):
        return _tp_row_proj(p, h, model_axis, delta32=delta(h, site))

    return proj, row_proj


def _serving_ln(ln, p, x):
    """LayerNorm over the last axis of ``(N, Hid)`` or ``(B, L, Hid)``.
    One query a row goes through a ``(N, 1, Hid)`` view: the operation
    order of the decode program the cell runs, whose lowered text is
    held fixed."""
    if x.ndim == 2:
        return ln.apply(p, x[:, None])[0][:, 0]
    return ln.apply(p, x)[0]


def _embed(lookup_w, pos_w, tokens, pos_rows):
    """Token rows (ids clipped into the vocabulary) plus the position
    rows of the view's queries (``pos_rows``, the first half of a
    view)."""
    import jax.numpy as jnp

    x = jnp.take(lookup_w, jnp.clip(tokens, 0, lookup_w.shape[0] - 1),
                 axis=0)
    return x + pos_rows(pos_w)


def _head(lnf, lnf_p, lin_p, x):
    """Final LayerNorm and the LM head: logits in the serving dtype.
    :func:`_log_probs` finishes them — apart, because the programs
    advance ``pos`` between the two."""
    return _serving_proj(lin_p, _serving_ln(lnf, lnf_p, x))


def _log_probs(logits):
    """float32 log-softmax, whatever the serving dtype."""
    import jax
    import jax.numpy as jnp

    return jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)


def _block(blk, bp, i, x, proj, row_proj, attend):
    """THE transformer block of every serving program, over ``(N, Hid)``
    (one query a row) and ``(B, L, Hid)`` alike: ``x + wo(attend(q, k,
    v))`` of ``ln1(x)``, then ``x + fc2(gelu(fc1(ln2(x))))``. ``proj`` /
    ``row_proj`` come from :func:`_proj_fns`; ``attend(i, q, k, v)`` is
    a cache view's second half: it gets ``q`` per head ``(…, heads,
    hd)`` and ``k``/``v`` as the stored rows ``(…, heads*hd)``, writes
    layer ``i``'s cache leaves and returns the context ``(…,
    heads*hd)``. A new layer kind is a fourth view or a config-driven
    norm in here, not a sixth program body."""
    import jax

    ap = bp[blk._child_key(1)]
    h = _serving_ln(blk.ln1, bp[blk._child_key(0)], x)
    q = proj(ap["wq"], h, f"wq{i}").reshape(
        *h.shape[:-1], -1, blk.attn.head_dim)
    k = proj(ap["wk"], h, f"wk{i}")
    v = proj(ap["wv"], h, f"wv{i}")
    x = x + row_proj(ap["wo"], attend(i, q, k, v), f"wo{i}")
    h2 = _serving_ln(blk.ln2, bp[blk._child_key(2)], x)
    hmid = jax.nn.gelu(proj(bp[blk._child_key(3)], h2, f"fc1{i}"))
    return x + row_proj(bp[blk._child_key(4)], hmid, f"fc2{i}")


# -- the three cache views. A view is ``(pos_rows, attend)``: where a
# query shape's tokens sit (their position-embedding rows) and, per
# layer, how it writes the cache and what it reads back — its write
# rule, its mask, its int8 branch. ``attend`` records the new ``k{i}`` /
# ``v{i}`` (/ ``_scale``) leaves in ``new_carry``, the program's output
# carry. The views stay separate on purpose: the B=1 programs are what
# generate() runs and what the tests hold the pooled programs against,
# and that independence lives in the write and the mask.


def _token_view(new_carry, active, max_len, scale, cache_dtype,
                kv_quant=False, rows_over=None):
    """View *token*: one query a row at the row's ``pos``, read through
    the single-query attention over the stored cache. ``active`` None:
    lockstep rows at the uniform ``pos[0]`` (:func:`make_decode_step`).
    ``active`` (N,) bool: pooled rows, each at its own ``pos[r]``, the
    inactive ones pure ballast (:func:`make_batch_decode_step`).
    ``rows_over`` ``(mesh, axis)``: the program is a plain jit whose
    rows XLA shards over that mesh axis by itself. On a TPU the pooled
    attention is a Mosaic kernel, which XLA refuses to partition
    ("cannot be automatically partitioned"): there each device runs it
    over the rows it holds, under a ``shard_map`` by rows (rows never
    interact: no collective)."""
    import jax.numpy as jnp
    from jax import lax

    from bigdl_tpu.ops.decode_attention import (
        decode_attention, folded_decode_attention,
    )
    from bigdl_tpu.utils.compat import auto_interpret, shard_map

    pos = new_carry["pos"]
    n = pos.shape[0]
    if active is None:
        # one dynamic_update_slice per tensor: no per-row gathers or
        # masked scatters on the path beam_search scans over
        t = pos[0]

        def pos_rows(pos_w):
            return lax.dynamic_index_in_dim(pos_w, t, keepdims=False)

        def attend(i, q, k_new, v_new):
            kc = lax.dynamic_update_slice_in_dim(
                new_carry[f"k{i}"], k_new[:, None].astype(cache_dtype), t, 1)
            vc = lax.dynamic_update_slice_in_dim(
                new_carry[f"v{i}"], v_new[:, None].astype(cache_dtype), t, 1)
            new_carry[f"k{i}"], new_carry[f"v{i}"] = kc, vc
            return folded_decode_attention(
                q, kc, vc, jnp.broadcast_to(t, (n,)), scale=scale,
                out_dtype=q.dtype).reshape(k_new.shape)

        return pos_rows, attend

    rows = jnp.arange(n)
    # write index per row: clamps to the last cache index rather than
    # silently wrapping
    wpos = jnp.clip(pos, 0, max_len - 1)

    def pos_rows(pos_w):
        return jnp.take(pos_w, wpos, axis=0)

    def attend_rows(q, kc, vc, at, on, *scales):
        return decode_attention(q, kc, vc, at, *scales, scale=scale,
                                out_dtype=q.dtype, active=on)

    if rows_over is not None and not auto_interpret():
        from jax.sharding import PartitionSpec

        mesh, axis = rows_over
        attend_rows = shard_map(
            attend_rows, mesh=mesh, in_specs=PartitionSpec(axis),
            out_specs=PartitionSpec(axis), check_vma=False)

    def attend(i, q, k_new, v_new):
        kc_prev, vc_prev = new_carry[f"k{i}"], new_carry[f"v{i}"]
        if kv_quant:
            # int8 storage: grow-only (slot, head) scale merge, then
            # the same masked scatter contract — inactive rows have
            # amax 0, so their scale, stored values, and the
            # written-back old value are all bitwise untouched
            k32 = k_new.astype(jnp.float32).reshape(q.shape)
            v32 = v_new.astype(jnp.float32).reshape(q.shape)
            k_amax = jnp.where(active[:, None],
                               jnp.max(jnp.abs(k32), axis=-1), 0.0)
            v_amax = jnp.where(active[:, None],
                               jnp.max(jnp.abs(v32), axis=-1), 0.0)
            (kc_prev, vc_prev, ks_new, vs_new, ks_safe,
             vs_safe) = _kv_quant_merge_step(
                kc_prev, vc_prev, new_carry[f"k{i}_scale"],
                new_carry[f"v{i}_scale"], k_amax, v_amax)
            k_wr0 = _kv_quantize(k32, ks_safe[..., None]
                                 ).reshape(k_new.shape)
            v_wr0 = _kv_quantize(v32, vs_safe[..., None]
                                 ).reshape(v_new.shape)
            new_carry[f"k{i}_scale"] = ks_new
            new_carry[f"v{i}_scale"] = vs_new
        else:
            k_wr0 = k_new.astype(cache_dtype)
            v_wr0 = v_new.astype(cache_dtype)
        # masked per-row scatter: inactive rows write their OLD value
        # back, so their cache stays bitwise identical
        k_old, v_old = kc_prev[rows, wpos], vc_prev[rows, wpos]
        k_wr = jnp.where(active[:, None], k_wr0, k_old)
        v_wr = jnp.where(active[:, None], v_wr0, v_old)
        kc = kc_prev.at[rows, wpos].set(k_wr)
        vc = vc_prev.at[rows, wpos].set(v_wr)
        new_carry[f"k{i}"], new_carry[f"v{i}"] = kc, vc
        # the pooled decode op, per-row masked single-query attention
        # over cols 0..wpos[r] of the stored 3-D array (a 4-D view here
        # costs two pool-sized copies per tensor per token on the TPU):
        # on a TPU the Pallas kernel, which fetches only the blocks an
        # ACTIVE row holds (int8 K/V loaded raw, dequant fused as two
        # scalar factors); elsewhere the whole-window jnp sums. Scores
        # accumulate fp32 regardless of the serving dtype
        scales = (new_carry[f"k{i}_scale"],
                  new_carry[f"v{i}_scale"]) if kv_quant else ()
        return attend_rows(q, kc, vc, wpos, active,
                           *scales).reshape(k_new.shape)

    return pos_rows, attend


def _fresh_prompt_view(new_carry, P, scale, cache_dtype, kv_quant=False):
    """View *fresh prompt* (:func:`make_prefill_step`): ``P`` columns
    from position 0 of a FRESH carry, K/V written at ``0..P-1``, dense
    causal attention over the prompt alone — ``(P, P)`` scores where the
    window view would attend over the full ``max_len`` cache (3x the
    attention work at P=127/max_len=384)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    causal = jnp.tril(jnp.ones((P, P), bool))

    def pos_rows(pos_w):
        return pos_w[:P]

    def attend(i, q, k, v):
        stored, dtype = k.shape, q.dtype       # (B, P, heads*hd)
        k, v = k.reshape(q.shape), v.reshape(q.shape)
        if kv_quant:
            # fresh carry (pos 0, scale 0): the degenerate one-shot
            # case of the grow-only merge — s_old is 0, so the
            # chunk's amax sets the scale (headroom included) and
            # the "requantized" zero cache passes through as zeros.
            # Routing through _kv_quant_merge keeps THE one copy of
            # the write rule honest.
            k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)
            kc_rq, ks, ks_safe = _kv_quant_merge(
                new_carry[f"k{i}"], new_carry[f"k{i}_scale"],
                jnp.max(jnp.abs(k32), axis=(1, 3)))
            vc_rq, vs, vs_safe = _kv_quant_merge(
                new_carry[f"v{i}"], new_carry[f"v{i}_scale"],
                jnp.max(jnp.abs(v32), axis=(1, 3)))
            kq = _kv_quantize(k32, ks_safe[:, None, :, None])
            vq = _kv_quantize(v32, vs_safe[:, None, :, None])
            # write into the REQUANTIZED cache (zeros requantize to
            # zeros on the fresh-carry contract, so this is free
            # here — but discarding kc_rq would silently corrupt any
            # future warm-carry caller the pos guard can't see,
            # e.g. under an outer trace)
            new_carry[f"k{i}"] = lax.dynamic_update_slice_in_dim(
                kc_rq, kq.reshape(stored), 0, 1)
            new_carry[f"v{i}"] = lax.dynamic_update_slice_in_dim(
                vc_rq, vq.reshape(stored), 0, 1)
            new_carry[f"k{i}_scale"] = ks
            new_carry[f"v{i}_scale"] = vs
            # attend over the dequantized values decode will read
            k = kq.astype(jnp.float32) * ks_safe[:, None, :, None]
            v = vq.astype(jnp.float32) * vs_safe[:, None, :, None]
            q = q.astype(jnp.float32)
        else:
            new_carry[f"k{i}"] = lax.dynamic_update_slice_in_dim(
                new_carry[f"k{i}"],
                k.astype(cache_dtype).reshape(stored), 0, 1)
            new_carry[f"v{i}"] = lax.dynamic_update_slice_in_dim(
                new_carry[f"v{i}"],
                v.astype(cache_dtype).reshape(stored), 0, 1)
        # scores accumulate fp32 like the decode step
        s = jnp.einsum("bqhd,bkhd->bhqk", q * scale, k,
                       preferred_element_type=jnp.float32)
        s = jnp.where(causal[None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32
                          ).astype(dtype).reshape(stored)

    return pos_rows, attend


def _window_view(new_carry, lengths, L, max_len, scale, cache_dtype,
                 kv_quant=False, deferred=None):
    """View *window* (:func:`make_batch_prefill_step`,
    :func:`make_batch_verify_step`): ``L`` query columns a row at the
    row's own offset ``pos[r]``, the first ``lengths[r]`` of them real,
    attending over the row's whole cache window. Returns ``(pos_rows,
    attend, rows, qpos)``. With ``kv_quant`` and a ``deferred`` list
    (the verify step) ``attend`` commits nothing: it appends the layer's
    fp32 ``(k, v)`` chunk to the list, for the caller's accepted-only
    commit."""
    import jax
    import jax.numpy as jnp

    start = new_carry["pos"]                       # (B,) per-row offset
    B = start.shape[0]
    rows = jnp.arange(B)
    qpos = start[:, None] + jnp.arange(L)[None]    # (B, L) absolute
    inb = jnp.arange(L)[None] < lengths[:, None]   # (B, L) valid mask
    # pad/overflow columns scatter to index max_len → dropped; valid
    # columns are in range (the callers' contract) and strictly
    # increasing per row, so writes never collide
    widx = jnp.where(inb, qpos, max_len)

    def pos_rows(pos_w):
        return jnp.take(pos_w, jnp.clip(qpos, 0, max_len - 1), axis=0)

    def attend(i, q, k, v):
        window = (B, max_len) + q.shape[2:]        # the cache, per head
        if not kv_quant:
            kc = new_carry[f"k{i}"].at[rows[:, None], widx].set(
                k.astype(cache_dtype), mode="drop")
            vc = new_carry[f"v{i}"].at[rows[:, None], widx].set(
                v.astype(cache_dtype), mode="drop")
            katt, vatt = kc.reshape(window), vc.reshape(window)
            new_carry[f"k{i}"], new_carry[f"v{i}"] = kc, vc
        else:
            k32 = k.astype(jnp.float32).reshape(q.shape)
            v32 = v.astype(jnp.float32).reshape(q.shape)
            if deferred is None:
                # int8 storage: per-(row, head) amax over the VALID
                # columns only (pad columns must not inflate the
                # scale), grow-only merge with the cached prefix's
                # scale, then the same dropped-index masked scatter
                inbf = inb[:, :, None, None]
                k_amax = jnp.max(jnp.abs(k32) * inbf, axis=(1, 3))
                v_amax = jnp.max(jnp.abs(v32) * inbf, axis=(1, 3))
                kc_rq, ks_new, ks_safe = _kv_quant_merge(
                    new_carry[f"k{i}"], new_carry[f"k{i}_scale"], k_amax)
                vc_rq, vs_new, vs_safe = _kv_quant_merge(
                    new_carry[f"v{i}"], new_carry[f"v{i}_scale"], v_amax)
                kc = kc_rq.at[rows[:, None], widx].set(
                    _kv_quantize(k32, ks_safe[:, None, :, None]
                                 ).reshape(k.shape), mode="drop")
                vc = vc_rq.at[rows[:, None], widx].set(
                    _kv_quantize(v32, vs_safe[:, None, :, None]
                                 ).reshape(v.shape), mode="drop")
                new_carry[f"k{i}_scale"] = ks_new
                new_carry[f"v{i}_scale"] = vs_new
                # the prompt attends over the DEQUANTIZED cache — the
                # values decode-time reads will see, so prefill and
                # decode stay one consistent numerics story
                katt = kc.reshape(window).astype(
                    jnp.float32) * ks_new[:, None, :, None]
                vatt = vc.reshape(window).astype(
                    jnp.float32) * vs_new[:, None, :, None]
                new_carry[f"k{i}"], new_carry[f"v{i}"] = kc, vc
            else:
                # the stored cache dequantized at its CURRENT
                # (pre-merge) scales with the chunk's own FLOAT K/V
                # overlaid in place
                ks_old = new_carry[f"k{i}_scale"]
                vs_old = new_carry[f"v{i}_scale"]
                katt = (new_carry[f"k{i}"].reshape(window).astype(
                            jnp.float32) * ks_old[:, None, :, None]).at[
                                rows[:, None], widx].set(k32, mode="drop")
                vatt = (new_carry[f"v{i}"].reshape(window).astype(
                            jnp.float32) * vs_old[:, None, :, None]).at[
                                rows[:, None], widx].set(v32, mode="drop")
                deferred.append((k32, v32))
        # queries attend over the row's FULL cache window (cached
        # prefix + this chunk) under an absolute causal mask; scores
        # accumulate fp32 regardless of the serving dtype
        att_dtype = jnp.float32 if kv_quant else cache_dtype
        qatt = (q * scale).astype(att_dtype)
        s = jnp.einsum("blhd,bmhd->bhlm", qatt, katt,
                       preferred_element_type=jnp.float32)
        valid = (jnp.arange(max_len)[None, None, None, :]
                 <= qpos[:, None, :, None])
        s = jnp.where(valid, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhlm,bmhd->blhd", p.astype(att_dtype), vatt,
                          preferred_element_type=jnp.float32
                          ).astype(q.dtype).reshape(k.shape)

    return pos_rows, attend, rows, qpos


def _check_tp_divisibility(model: Sequential, heads: int, tp: int) -> None:
    """Fail fast (with the fix in the message) when a model cannot split
    over a ``tp``-way model axis: whole heads and whole MLP hidden rows
    must land on each chip."""
    if tp <= 0:
        raise ValueError(f"model-axis size must be positive, got {tp}")
    hidden = model.modules[1].hidden_size
    mlp_hidden = None
    for m in model.modules:
        inner = m.modules[0] if isinstance(m, Remat) else m
        if isinstance(inner, TransformerBlock):
            mlp_hidden = inner.fc1.output_size
            break
    if heads % tp:
        raise ValueError(
            f"n_heads {heads} not divisible by the model-axis size {tp} "
            "— tensor-parallel serving shards whole heads")
    if mlp_hidden is not None and mlp_hidden % tp:
        raise ValueError(
            f"MLP hidden {mlp_hidden} not divisible by the model-axis "
            f"size {tp}")
    if hidden % tp:
        raise ValueError(
            f"hidden {hidden} not divisible by the model-axis size {tp}")


def _serving_meta(model: Sequential, compute_dtype, mesh=None,
                  model_axis: str = "model"):
    """What every serving factory reads off the model at build time,
    from the UNCAST params (structure only, no weight copy). With a
    ``mesh``, also the fail-fast check that the model splits over its
    model axis."""
    from types import SimpleNamespace

    import jax.numpy as jnp

    from bigdl_tpu.nn.misc import LookupTable

    model._ensure_params()
    mods = model.modules
    assert isinstance(mods[0], LookupTable), "TransformerLM-shaped model"
    off = _decode_head_offset(model)
    blocks0 = _resolve_decode_views(model, off, model.params)[2]
    attn0 = blocks0[0][0].attn
    if mesh is not None:
        _check_tp_divisibility(model, attn0.n_heads,
                               int(mesh.shape[model_axis]))
    return SimpleNamespace(
        off=off, lnf=mods[-2 - off], n_layers=len(blocks0),
        max_len=mods[1].max_len, vocab=mods[0].n_index,
        heads=attn0.n_heads, hd=attn0.head_dim,
        scale=attn0.head_dim ** -0.5,
        cache_dtype=compute_dtype or jnp.float32)


def _captured_params(model: Sequential, compute_dtype):
    """``get()`` -> the build-time weights cast for serving, made on the
    first call only: the ``params=None`` mode of the B=1 steps, which
    bakes them into the program as constants."""
    cache: list = []

    def get():
        if not cache:
            cache.append(_cast_keep_scales(model.params, compute_dtype))
        return cache[0]

    return get


def _shard_step(fn, model: Sequential, mesh, data_axis, model_axis: str,
                cspecs, n_out: int, knobs: bool = False, adapter=None):
    """THE ``shard_map`` lowering of the pooled programs — the
    tensor-parallel serving plane (``bigdl_tpu.serving.sharded``). Each
    takes ``(params, rows, rows, carry[, knobs][, adapter_ids, bank])``
    and returns ``n_out`` row arrays and the carry; ``cspecs`` is the
    carry's spec tree (:func:`serving_carry_specs`). Params shard
    Megatron-style (:func:`tp_param_specs`) and so does the adapter
    bank with the weights it adapts; row arrays, knobs and adapter ids
    shard over ``data_axis``, or replicate when it is None (the batched
    prefill: its rows are few and short-lived, so sharding them would
    buy little and break the B=1 prefix-cache path).

    check_vma off: sampled tokens and non-head state are REPLICATED over
    the model axis (every model chip computes the identical post-psum
    value deterministically), which the static replication checker
    cannot prove through the sampler's vmapped random.split."""
    from jax.sharding import PartitionSpec as P

    from bigdl_tpu.serving.sampling import knob_partition_specs
    from bigdl_tpu.utils.compat import shard_map

    row = P() if data_axis is None else P(data_axis)
    in_specs = (tp_param_specs(model, model_axis), row, row, cspecs)
    if knobs:
        in_specs += (knob_partition_specs(data_axis),)
    if adapter is not None:
        in_specs += (row, adapter_bank_specs(model, model_axis))
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=(row,) * n_out + (cspecs,), check_vma=False)


def _serving_init_carry(n_layers: int, max_len: int, heads: int, hd: int,
                        cache_dtype, kv_quant: bool, sampling: bool,
                        vocab: int):
    """THE one pooled-carry layout: per-layer K/V rows
    ``(n_slots, max_len, heads*hd)`` (head-major lanes: the
    ``(…, heads, hd)`` array with its two minor axes merged, which is
    the shape the decode step's row scatter and whole-pool read agree
    on — no program that holds the pool re-lays it out) + per-row
    ``pos``, int8 dequant scales on the quantized layout, and the per-row
    sampling state (RNG lanes + penalty counters — the engine seeds rows
    at admission via ``KVPool.write_sampling``). Shared by
    :func:`make_batch_decode_step` and :func:`make_batch_verify_step` so
    a pool built by either hands its carry to the other unchanged (the
    speculative engine's verify step IS its decode step)."""
    import jax.numpy as jnp

    def init_carry(n_slots: int):
        carry = {"pos": jnp.zeros((n_slots,), jnp.int32)}
        kv_dt = jnp.int8 if kv_quant else cache_dtype
        for i in range(n_layers):
            carry[f"k{i}"] = jnp.zeros((n_slots, max_len, heads * hd),
                                       kv_dt)
            carry[f"v{i}"] = jnp.zeros((n_slots, max_len, heads * hd),
                                       kv_dt)
            if kv_quant:
                # per-(slot, head) dequant scales; 0 = "no scale yet"
                # (fresh rows — the first write establishes it)
                carry[f"k{i}_scale"] = jnp.zeros((n_slots, heads),
                                                 jnp.float32)
                carry[f"v{i}_scale"] = jnp.zeros((n_slots, heads),
                                                 jnp.float32)
        if sampling:
            carry["rng"] = jnp.zeros((n_slots, 2), jnp.uint32)
            carry["tok_counts"] = jnp.zeros((n_slots, vocab), jnp.int32)
            carry["prompt_mask"] = jnp.zeros((n_slots, vocab), bool)
        return carry

    return init_carry


def make_prefill_step(model: Sequential, compute_dtype=None,
                      kv_quant: bool = False):
    """ONE-pass prompt ingestion for the KV-cached decoder (the serving
    "prefill" phase). Returns ``prefill(params, tokens, carry) ->
    (logprobs_last, carry)``:

    * ``tokens``: (B, P) 0-based prompt ids, P ≤ max_len (static shape —
      re-jit per length bucket). EQUAL-LENGTH prompts only: there is no
      per-row length mask, so right-padding a shorter prompt would write
      pad tokens into its cache and score the pad position (batch rows
      must share one true length; ragged batches go through
      :func:`make_batch_prefill_step`, which masks per row);
    * ``carry`` must be FRESH (``carry['pos'] == 0`` everywhere, straight
      from ``init_carry``): prefill writes K/V at positions 0..P-1 and
      forces ``pos = P`` unconditionally, so a partially-filled carry
      would be silently corrupted. The returned wrapper raises on a
      non-zero concrete ``pos`` before entering jit (skipped under an
      outer trace, where the value is abstract);
    * the whole prompt runs as ONE causal forward (parallel over P, full
      MXU tiles) and the per-layer K/V land in the carry at positions
      0..P-1 with ``pos`` set to P — decoding continues with the
      :func:`make_decode_step` step.

    Replaces priming the cache with P sequential single-token decode
    steps, each of which re-reads every weight: at 137M/P=128 that is
    ~74 ms of weight traffic vs one ~6 ms forward (measured in
    benchmarks/decode_bench.py). ``params`` follows the same runtime-
    argument convention as the decode step (``serving_params``).

    ``kv_quant=True`` writes the cache int8 with (row, head) scales and
    runs the prompt's own attention over the dequantized values,
    mirroring :func:`make_batch_prefill_step`. The block is
    :func:`_block` under the *fresh prompt* view
    (:func:`_fresh_prompt_view`); test_prefill_matches_sequential_decode
    holds cache and logits against the decode step for plain, bf16 and
    int8 models."""
    import jax
    import jax.numpy as jnp

    m = _serving_meta(model, compute_dtype)
    get_p0 = _captured_params(model, compute_dtype)
    proj, row_proj = _proj_fns()

    def prefill(params, tokens, carry):
        Pt = get_p0() if params is None else \
            _cast_keep_scales(params, compute_dtype)
        lookup_w, pos_w, blocks, lnf_p, lin_p = \
            _resolve_decode_views(model, m.off, Pt)
        P = tokens.shape[1]
        if P > m.max_len:
            raise ValueError(
                f"prompt length {P} exceeds max_len {m.max_len}")
        new_carry = dict(carry)
        pos_rows, attend = _fresh_prompt_view(
            new_carry, P, m.scale, m.cache_dtype, kv_quant)
        x = _embed(lookup_w, pos_w, tokens, pos_rows)     # (B, P, Hid)
        for i, (blk, bp) in enumerate(blocks):
            x = _block(blk, bp, i, x, proj, row_proj, attend)
        logits = _head(m.lnf, lnf_p, lin_p, x[:, P - 1])  # last position
        new_carry["pos"] = jnp.full_like(carry["pos"], P)
        return _log_probs(logits), new_carry

    jitted = jax.jit(prefill)

    def prefill_checked(params, tokens, carry):
        import numpy as np

        from bigdl_tpu.serving.metrics import span

        # the span wraps the BODY (fences.SPAN_NAMES): host guards, a
        # small readback and the program's LAUNCH — never its device
        # time, which is the jit_prefill program's in the trace
        with span("prefill.launch", rows=tokens.shape[0],
                  padded=tokens.shape[0], bucket=tokens.shape[-1]):
            pos = carry["pos"]
            # fresh-carry contract (see docstring): cheap concrete-value
            # check outside jit; under an outer trace pos is abstract and
            # the check is skipped (the (B,) int32 host readback costs
            # microseconds)
            if not isinstance(pos, jax.core.Tracer) \
                    and np.asarray(pos).any():
                raise ValueError(
                    "make_prefill_step requires a fresh carry "
                    "(carry['pos'] must be all zeros): prefill writes K/V "
                    "at positions 0..P-1 and resets pos, which would "
                    "corrupt a partially-filled cache (got "
                    f"pos={np.asarray(pos).tolist()})")
            return jitted(params, tokens, carry)

    # exposed so benchmarks/tests can count compiled (B, P) buckets
    prefill_checked._jitted = jitted
    return prefill_checked


def make_batch_prefill_step(model: Sequential, compute_dtype=None,
                            mesh=None, data_axis: str = "data",
                            model_axis: str = "model",
                            carry_sampling: bool = False,
                            kv_quant: bool = False,
                            adapter=None):
    """MASKED multi-row prompt ingestion: one compiled program prefills a
    whole RAGGED batch of prompts (the admission path of
    ``bigdl_tpu.serving`` — see ``serving/admission.py``). Returns
    ``prefill(params, tokens, lengths, carry) -> (logprobs_last, carry)``:

    * ``tokens``: (B, L) 0-based ids, each row RIGHT-PADDED to the
      length bucket L (pad values are ignored — clip to vocab range is
      applied, any filler works);
    * ``lengths``: (B,) int32 — row r's true token count (0 ≤ lengths[r]
      ≤ L). Rows with ``lengths[r] == 0`` are pure ballast: their cache
      and ``pos`` are bitwise untouched and their logprob row is garbage
      the caller must ignore (exactly the batch-decode ``active``
      convention, so one (B, L) program serves every occupancy);
    * ``carry``: a B-row :func:`make_batch_decode_step` carry.
      ``carry['pos'][r]`` is row r's START offset: 0 for a fresh prompt,
      ``p0 > 0`` to CONTINUE over ``p0`` already-cached positions (the
      shared-prefix path: a prefix-cache hit clones the cached carry and
      prefills only the suffix). Row r writes K/V at absolute positions
      ``pos[r]..pos[r]+lengths[r]-1`` and its ``pos`` advances by
      ``lengths[r]``;
    * returns per-row log-probs of each row's LAST VALID position (the
      next-token distribution after the prompt) and the updated carry.

    The block is :func:`_block` under the *window* view
    (:func:`_window_view`, which owns the masking): one program shape
    per (B, L) regardless of per-row lengths or start offsets. That
    bounds the compiled-program set by the bucket count where per-row
    :func:`make_prefill_step` calls compile per DISTINCT LENGTH (the
    PR-1 admission stall — see docs/serving.md). The tradeoff: scores
    span ``(L, max_len)`` instead of ``(P, P)``, so for one lone short
    prompt the per-row step does less work; the win is batching ragged
    admissions into one call (and it is what keeps a sharded prefill
    program reusable — shape-stable admission).

    The wrapper raises (on concrete values) if a row would write past
    ``max_len`` (``pos[r] + lengths[r] > max_len``) or ``lengths``
    exceeds L. Numerics follow the serving conventions (fp32 score
    accumulation, ``compute_dtype`` cache, int8 weight-only
    projections); per-row results equal :func:`make_prefill_step` to
    float round-off — the wider masked reduction can reorder XLA sums —
    pinned by tests/test_serving_admission.py.

    ``mesh``, ``kv_quant`` and ``adapter`` are
    :func:`make_batch_decode_step`'s, and the carry that comes back is
    that step's. What differs: under a mesh tokens/lengths/carry rows
    stay REPLICATED over ``data_axis`` (:func:`_shard_step`), and
    ``carry_sampling`` names the sampling leaves of such a pool's carry,
    which ride through untouched; with ``kv_quant`` a suffix
    continuation over a quantized cached prefix requantizes the prefix
    when the suffix raises the scale, and zero-length rows still pass
    through bitwise (amax 0, and their scatter drops); with an
    ``adapter`` the call is ``prefill(params, tokens, lengths, carry,
    adapter_ids, bank)``."""
    import jax
    import jax.numpy as jnp

    m = _serving_meta(model, compute_dtype, mesh, model_axis)
    max_len = m.max_len

    def prefill(params, tokens, lengths, carry, adapter_ids=None,
                bank=None):
        Pt = _cast_keep_scales(params, compute_dtype)
        lookup_w, pos_w, blocks, lnf_p, lin_p = \
            _resolve_decode_views(model, m.off, Pt)
        proj, row_proj = _proj_fns(adapter, adapter_ids, bank, mesh,
                                   model_axis)
        L = tokens.shape[1]
        start = carry["pos"]
        new_carry = dict(carry)
        pos_rows, attend, rows, _ = _window_view(
            new_carry, lengths, L, max_len, m.scale, m.cache_dtype,
            kv_quant)
        x = _embed(lookup_w, pos_w, tokens, pos_rows)     # (B, L, Hid)
        for i, (blk, bp) in enumerate(blocks):
            x = _block(blk, bp, i, x, proj, row_proj, attend)
        # each row's next-token logits come from its LAST VALID position
        last = jnp.clip(lengths - 1, 0, L - 1)
        logits = _head(m.lnf, lnf_p, lin_p, x[rows, last])
        new_carry["pos"] = start + lengths.astype(start.dtype)
        return _log_probs(logits), new_carry

    if mesh is None:
        jitted = jax.jit(prefill)
    else:
        jitted = jax.jit(_shard_step(
            prefill, model, mesh, None, model_axis,
            serving_carry_specs(model, sampling=carry_sampling,
                                data_axis=None, model_axis=model_axis,
                                kv_quant=kv_quant),
            n_out=1, adapter=adapter))

    def prefill_checked(params, tokens, lengths, carry, *adapter_args):
        import numpy as np

        from bigdl_tpu.serving.metrics import span

        # the span wraps the BODY (fences.SPAN_NAMES): host guards, their
        # small readback and the program's LAUNCH — never its device
        # time, which is the jit_prefill program's in the trace
        with span("prefill.launch", padded=tokens.shape[0],
                  bucket=tokens.shape[-1]) as sp:
            lengths = jnp.asarray(lengths, jnp.int32)
            if tokens.ndim != 2 or lengths.shape != tokens.shape[:1]:
                raise ValueError(
                    f"tokens must be (B, L) with lengths (B,): got "
                    f"{tokens.shape} / {lengths.shape}")
            if carry["pos"].shape[0] != tokens.shape[0]:
                raise ValueError(
                    f"carry has {carry['pos'].shape[0]} rows but tokens has "
                    f"{tokens.shape[0]} — the carry must come from "
                    "make_batch_decode_step's init_carry(B)")
            pos = carry["pos"]
            # cheap concrete-value guards outside jit (abstract under an
            # outer trace, where they are skipped): a row writing past the
            # cache would be silently DROPPED by the masked scatter
            if not isinstance(lengths, jax.core.Tracer) \
                    and not isinstance(pos, jax.core.Tracer):
                ln, ps = np.asarray(lengths), np.asarray(pos)
                sp.note(rows=int(np.count_nonzero(ln)))
                if (ln < 0).any() or (ln > tokens.shape[1]).any():
                    raise ValueError(
                        f"lengths must lie in 0..L={tokens.shape[1]} "
                        f"(got {ln.tolist()})")
                if (ps + ln > max_len).any():
                    raise ValueError(
                        f"rows would write past max_len {max_len}: "
                        f"pos={ps.tolist()} + lengths={ln.tolist()}")
            if adapter is not None and len(adapter_args) != 2:
                raise ValueError(
                    "this prefill step was built with an adapter spec — "
                    "call it as prefill(params, tokens, lengths, carry, "
                    "adapter_ids, bank)")
            return jitted(params, tokens, lengths, carry, *adapter_args)

    # exposed so benchmarks/tests can count compiled (B, L) buckets
    prefill_checked._jitted = jitted
    return prefill_checked


def make_decode_step(model: Sequential, compute_dtype=None):
    """KV-cached incremental decoding for a trained :func:`TransformerLM`.

    Returns ``(step_fn, init_carry)``:

    * ``init_carry(batch) -> carry`` — per-layer K/V caches
      ``(batch, max_len, heads*head_dim)`` (head-major lanes — the
      pooled layout, see :func:`_serving_init_carry`) plus a position
      counter;
    * ``step_fn(params, tokens, carry) -> (logprobs, carry)`` —
      one token per call, attention reads the cache (O(1) new compute per
      step instead of re-running the full prefix). ``params`` may be
      ``None`` (use the weights captured at build time — convenient, but
      jit bakes them into the program as CONSTANTS, so the compiled
      executable carries the full weight payload and a weight update
      means a recompile) or the model's
      params pytree passed as a RUNTIME argument — the serving mode:
      weights live in device buffers, update without recompiling, and the
      program stays small (benchmarks/decode_bench.py uses this). The
      signature matches ``SequenceBeamSearch``/
      :func:`bigdl_tpu.nn.beam_search.beam_search`; beam
      parent-gathering permutes whole cache rows, and the position
      counter is uniform across rows, so lockstep decoding stays exact.

    Tokens are 0-based class indices (logit column c ↔ 1-based word id
    c+1), matching the LM's LogSoftMax output columns.

    ``compute_dtype`` (e.g. ``jnp.bfloat16``) is the serving-precision
    knob: captured weights and K/V caches store/compute in that dtype
    (decode is weight-read-bound, so halving weight bytes is the
    first-order lever — measured in benchmarks/decode_bench.py); score
    accumulation and the final log-softmax stay fp32. Quantized models
    (``Quantizer.quantize(lm, scheme="weight_only")``) decode through the
    same step — projections whose params carry ``weight_q`` run the int8
    dequant-into-matmul path, compounding with ``compute_dtype``.

    The block is :func:`_block` under the lockstep *token* view
    (:func:`_token_view` with no ``active`` mask).
    """
    import jax

    m = _serving_meta(model, compute_dtype)
    get_p0 = _captured_params(model, compute_dtype)
    proj, row_proj = _proj_fns()
    init_carry = _serving_init_carry(m.n_layers, m.max_len, m.heads, m.hd,
                                     m.cache_dtype, kv_quant=False,
                                     sampling=False, vocab=0)

    def step(params, tokens, carry):
        Pt = get_p0() if params is None else \
            _cast_keep_scales(params, compute_dtype)
        lookup_w, pos_w, blocks, lnf_p, lin_p = \
            _resolve_decode_views(model, m.off, Pt)
        new_carry = dict(carry)
        pos_rows, attend = _token_view(new_carry, None, m.max_len, m.scale,
                                       m.cache_dtype)
        x = _embed(lookup_w, pos_w, tokens, pos_rows)     # (N, Hid)
        for i, (blk, bp) in enumerate(blocks):
            x = _block(blk, bp, i, x, proj, row_proj, attend)
        logits = _head(m.lnf, lnf_p, lin_p, x)
        new_carry["pos"] = carry["pos"] + 1
        return _log_probs(logits), new_carry

    # shapes are static across steps: compile once, reuse every token
    # (composes with beam_search's lax.scan — jit-of-jit inlines)
    return jax.jit(step), init_carry


def make_batch_decode_step(model: Sequential, compute_dtype=None,
                           sampling: bool = False, mesh=None,
                           data_axis: str = "data",
                           model_axis: Optional[str] = "model",
                           kv_quant: bool = False,
                           adapter=None):
    """Per-ROW-position decode step for continuous batching
    (``bigdl_tpu.serving``): every cache row advances independently, so
    one pooled carry can hold many requests at different depths and rows
    can be recycled mid-flight.

    Returns ``(step_fn, init_carry)``:

    * ``init_carry(n_slots) -> carry`` — identical layout to
      :func:`make_decode_step` (per-layer ``(N, max_len, heads*hd)``
      K/V + ``pos``), but ``pos`` is PER-ROW state, not uniform;
    * ``step_fn(params, tokens, active, carry) -> (logprobs, carry)`` —
      ``tokens`` (N,) 0-based ids, ``active`` (N,) bool. Active rows
      write K/V at their own ``pos[r]``, attend over ``0..pos[r]`` of
      their own cache row, and advance ``pos[r]`` by one; inactive rows
      are pure ballast — their cache and ``pos`` are bitwise untouched
      (the write scatters the OLD value back) and their logprob rows are
      garbage the caller must ignore. Rows never interact (attention is
      per-row over the row's own cache), so each active row computes the
      same math as the single-request :func:`make_decode_step` (equal to
      float round-off — batch shape changes XLA reduction order).

    ``sampling=True`` fuses a per-row SAMPLE-FROM-LOGITS epilogue
    (:func:`bigdl_tpu.serving.sampling.sample_rows`) into the step:

    * the carry grows per-row sampling state — ``rng`` (N, 2) uint32
      RNG lanes, ``tok_counts`` (N, vocab) int32 generated-token
      counts, ``prompt_mask`` (N, vocab) bool prompt membership (the
      engine seeds these per admission via ``KVPool.write_sampling``);
    * the signature becomes ``step_fn(params, tokens, active, carry,
      knobs) -> (token, chosen_logp, carry)`` — ``knobs`` is the
      per-row array dict of :func:`~bigdl_tpu.serving.sampling.
      make_knob_rows` (temperature/top-k/top-p/penalties/ban rows, all
      runtime VALUES: one compiled program covers every knob mix, and
      ``temperature == 0`` rows reduce to exact argmax);
    * the ``(N, vocab)`` distribution never crosses to host — only the
      chosen token ids and their raw model log-probs do, preserving the
      one-small-readback-per-step property the greedy step had;
    * inactive rows stay bitwise untouched (rng/counts included); their
      token/log-prob outputs are garbage the caller must ignore.

    The block is :func:`_block`, shared with :func:`make_decode_step`;
    what differs is the pooled *token* view (:func:`_token_view` with
    the ``active`` mask): per-row gathers and masked scatters that stay
    off the lockstep path. test_batch_decode_step_matches_single_row and
    the engine-vs-generate parity tests (plain + bf16) hold the two
    views against each other.

    ``params``/``compute_dtype`` follow the :func:`make_decode_step`
    conventions (runtime params tree via :func:`serving_params`, fp32
    score accumulation, int8 weight-only projections supported).
    The caller owns slot assignment and must keep ``pos[r] < max_len``
    for active rows (writes clamp to the last cache index rather than
    silently wrapping).

    ``mesh`` (a ``jax.sharding.Mesh`` with ``data_axis`` and
    ``model_axis``) lowers the step through :func:`_shard_step`
    instead of a bare jit: slot rows shard over ``data_axis``,
    attention heads + MLP hidden over ``model_axis`` with the Megatron
    two-collectives-per-block layout (:func:`_proj_fns`; see
    ``parallel/tensor_parallel.py``). Callers place params with
    :func:`tp_param_specs` and the carry with
    :func:`serving_carry_specs`; requires ``n_heads`` and
    ``mlp_ratio*hidden`` divisible by the model-axis size, float (non-
    quantized) weights, and no layer_scan. Per-row math is unchanged —
    only the two closing psums reorder float sums, so outputs match the
    unsharded step to round-off. A mesh whose model axis is ONE wide
    (a slot-data-parallel-only plane), or ``model_axis=None`` (weights
    replicated over every axis: the speculative DRAFT's step on any
    plane), skips that ``shard_map``: the step is the bare jit, which
    XLA partitions by rows, bitwise identical to the unsharded step
    (pinned by tests/test_serving_sharded.py); the mesh only tells the
    pooled attention which rows a device holds (:func:`_token_view`,
    ``rows_over``). Every pooled decode step of a plane takes the
    plane's mesh: without it the step does not lower for a TPU mesh.

    ``kv_quant=True`` stores the per-layer K/V caches as INT8 with one
    fp32 scale per (slot, head) (carry keys ``k{i}_scale``/
    ``v{i}_scale``, shape ``(N, heads)`` — ~overhead-free next to the
    halved cache payload). Writes quantize through the grow-only scale
    merge (:func:`_kv_quant_merge`; inactive rows pass through bitwise,
    preserving the ballast contract above). The attention read routes
    through :func:`bigdl_tpu.ops.decode_attention.decode_attention`
    with the dequantization FUSED into the K/V load (scales factor out
    of both contractions exactly, so int8 bytes are what cross HBM).
    Quantization is an engine-level storage choice, not per-row state:
    a ``kv_quant`` step is still ONE compiled program for every
    traffic mix, same as the float step (pinned by
    tests/test_serving_kv_quant.py).

    ``adapter`` (a :class:`~bigdl_tpu.serving.lora.AdapterSpec`) selects
    the multi-tenant variant: the signature grows a trailing
    ``(adapter_ids, bank)`` pair — ``adapter_ids`` (N,) int32 per-row
    bank-slot ids, ``bank`` the AdapterBank's device-array dict — and
    every block's six projections add the rows' gathered low-rank delta
    (``_adapter_delta``; bank row 0 is the all-zeros NULL adapter, so
    base rows add an exact 0.0 and mixed base/tenant traffic is the
    same ONE compiled program). Under a mesh the two-collectives-per-
    block budget is unchanged (see :func:`adapter_bank_specs`).
    """
    import jax
    import jax.numpy as jnp

    # no model axis, or one that is one wide: the mesh shards the slot
    # rows alone. The program stays a plain jit that XLA partitions,
    # and only the pooled attention is told which rows a device holds
    rows_over = None
    if mesh is not None and (model_axis is None
                             or int(mesh.shape[model_axis]) == 1):
        rows_over, mesh = (mesh, data_axis), None
    m = _serving_meta(model, compute_dtype, mesh, model_axis)
    init_carry = _serving_init_carry(m.n_layers, m.max_len, m.heads, m.hd,
                                     m.cache_dtype, kv_quant, sampling,
                                     m.vocab)

    def step(params, tokens, active, carry, adapter_ids=None, bank=None):
        Pt = _cast_keep_scales(params, compute_dtype)
        lookup_w, pos_w, blocks, lnf_p, lin_p = \
            _resolve_decode_views(model, m.off, Pt)
        proj, row_proj = _proj_fns(adapter, adapter_ids, bank, mesh,
                                   model_axis)
        new_carry = dict(carry)
        pos_rows, attend = _token_view(new_carry, active, m.max_len,
                                       m.scale, m.cache_dtype, kv_quant,
                                       rows_over)
        x = _embed(lookup_w, pos_w, tokens, pos_rows)     # (N, Hid)
        for i, (blk, bp) in enumerate(blocks):
            x = _block(blk, bp, i, x, proj, row_proj, attend)
        logits = _head(m.lnf, lnf_p, lin_p, x)
        new_carry["pos"] = carry["pos"] + active.astype(jnp.int32)
        return _log_probs(logits), new_carry

    def sample_step(params, tokens, active, carry, knobs,
                    adapter_ids=None, bank=None):
        # fused sampling epilogue: (N, vocab) log-probs reduce to a
        # per-row token + raw-model log-prob on device (sampling.py is
        # imported lazily — serving imports models, not vice versa)
        from bigdl_tpu.serving.sampling import sample_rows

        logp, new_carry = step(params, tokens, active, carry,
                               adapter_ids, bank)
        tok, chosen, new_keys, new_counts = sample_rows(
            logp, carry["rng"], knobs, carry["tok_counts"],
            carry["prompt_mask"], active)
        # inactive rows: rng/counts bitwise untouched, same contract as
        # the K/V scatter
        new_carry["rng"] = jnp.where(active[:, None], new_keys,
                                     carry["rng"])
        new_carry["tok_counts"] = jnp.where(active[:, None], new_counts,
                                            carry["tok_counts"])
        return tok, chosen, new_carry

    fn = sample_step if sampling else step
    if mesh is not None:
        fn = _shard_step(
            fn, model, mesh, data_axis, model_axis,
            serving_carry_specs(model, sampling=sampling,
                                data_axis=data_axis, model_axis=model_axis,
                                kv_quant=kv_quant),
            n_out=2 if sampling else 1, knobs=sampling, adapter=adapter)
    # the carry is DONATED: the engine replaces its pooled carry with the
    # step's output every token, and without donation XLA materializes a
    # complete second copy of the whole KV pool per generated token
    # (~300 MB/step at 137M/8 slots). Callers must not touch the input
    # carry after a step — read it (np.asarray) before stepping.
    return jax.jit(fn, donate_argnums=(3,)), init_carry


def make_batch_verify_step(model: Sequential, compute_dtype=None,
                           width: int = 4, mesh=None,
                           data_axis: str = "data",
                           model_axis: str = "model",
                           kv_quant: bool = False,
                           adapter=None):
    """Speculative DRAFT-AND-VERIFY step for the serving engine
    (``bigdl_tpu.serving.speculative``): one compiled program scores a
    per-row CHUNK of candidate tokens against the target model and
    advances each row by however many the target confirms — the
    multi-token generalization of :func:`make_batch_decode_step`. It is
    the batched prefill's block and cache view (:func:`_block` under
    :func:`_window_view`: per-row start offsets already express
    "continue this row's suffix"); this factory adds what only it has:
    EVERY chunk position's distribution through the per-row sampler,
    the acceptance chain, and the deferred accepted-only int8 commit.

    Returns ``(verify_fn, init_carry)``; ``init_carry`` builds exactly
    the :func:`make_batch_decode_step` ``sampling=True`` carry (shared
    layout — a pool built by either hands its carry to the other).
    ``mesh``, ``kv_quant`` and ``adapter`` are that step's too (chunk
    outputs replicate over the model axis like its sampled tokens; the
    target scores each row under that ROW'S adapter, and the engine
    pins drafts to the null adapter, see serving/speculative.py).

    ``verify_fn(params, tokens, lengths, carry, knobs[, adapter_ids,
    bank]) -> (tokens_out, logps_out, n_emit, carry)``:

    * ``tokens``: (N, ``width``) 0-based ids — row r's column 0 is its
      current decode input (the engine's ``next_token``), columns
      ``1..lengths[r]-1`` are DRAFT proposals for the following
      positions; columns at and beyond ``lengths[r]`` are pad;
    * ``lengths``: (N,) int32, ``0 <= lengths[r] <= width`` — how many
      chunk positions row r runs this step (``k_r`` drafts + 1), runtime
      VALUES of the one (N, width) program. ``lengths[r] == 1`` is
      EXACTLY the plain sampled decode step, so a normal row in a mixed
      batch costs nothing extra; ``lengths[r] == 0`` rows are ballast
      (the ``active`` convention). The caller keeps ``pos[r] +
      lengths[r] <= max_len`` (the engine enforces it): columns out of
      range would be silently dropped by the masked scatter;
    * ``knobs``: the per-row sampling knob dict
      (:func:`~bigdl_tpu.serving.sampling.make_knob_rows`);
    * ``tokens_out``/``logps_out``: (N, width) — position j's token is
      drawn by THE one per-row sampler
      (:func:`~bigdl_tpu.serving.sampling.sample_rows`) from the
      target's next-token distribution after chunk inputs ``0..j``,
      with the row's RNG lane split once per position IN ORDER and
      penalty counts updated per draw — the math the plain decode step
      would do had the accepted prefix been fed token by token.
      (Numerics caveat: the chunked path rounds reduced-precision
      activations in a different order than the single-token step, so
      at bf16 an argmax on a sub-rounding near-tie can flip against the
      baseline; fp32 parity is exact on the dev box, and the parity
      tests hold token identity where the gaps are real);
    * ``n_emit``: (N,) int32 — ``1 + (leading positions whose drawn
      token equals the NEXT chunk input)``: position j's draw is a
      valid emission iff drafts ``1..j`` all matched the draws before
      them (its context is then the true emitted stream); the first
      mismatch still emits — its draw came from the correct conditional
      — and everything after it is discarded. Temperature-0 rows: the
      standard greedy verification, token-identical to the baseline
      engine. Sampled rows: the EMITTED stream equals the baseline
      engine's draw for draw (same lane splits, same conditionals — the
      draft only controls how many draws land per step), which is what
      makes fixed seeds replay across speculative/normal engines and
      eviction/readmission. (Traded away on purpose: Leviathan-style
      rejection sampling, which consumes randomness in a draft-
      dependent pattern and cannot replay the baseline stream;
      acceptance rate is ``P(draft == the sampler's draw)``.)

    Rollback is pointer arithmetic, not a cache rewrite: K/V for ALL
    ``lengths[r]`` inputs are written at ``pos[r]..``, but ``pos`` — and
    the RNG lane and the penalty counts — advance by ``n_emit[r]`` only;
    what lies past the accepted prefix is stale bytes BEHIND ``pos``,
    invisible to every later step (the masking that makes recycled
    slots safe) and overwritten as decoding proceeds.

    ``kv_quant`` merges ACCEPTED COLUMNS ONLY: the chunk's attention
    reads the stored cache dequantized at its CURRENT scales with the
    chunk's float K/V overlaid, and the grow-only scale merge + the
    quantized scatter wait until ``n_emit`` is known, so a REJECTED
    draft can never touch a row's scales or stored bytes: two steps
    from one state whose accepted outcome agrees return BITWISE-
    identical carries whatever their rejected columns held
    (tests/test_serving_kv_quant.py::
    test_int8_draft_independence_exact). The trade: in-step attention
    sees the chunk's own K/V unrounded where the plain decode step
    reads the current token int8-roundtripped, so int8 spec-vs-baseline
    parity is a pinned-config contract.
    """
    import jax
    import jax.numpy as jnp

    if width < 1:
        raise ValueError(f"width must be >= 1, got {width}")
    m = _serving_meta(model, compute_dtype, mesh, model_axis)
    max_len = m.max_len
    S = int(width)
    init_carry = _serving_init_carry(m.n_layers, max_len, m.heads, m.hd,
                                     m.cache_dtype, kv_quant, True,
                                     m.vocab)

    def verify(params, tokens, lengths, carry, knobs, adapter_ids=None,
               bank=None):
        from bigdl_tpu.serving.sampling import sample_rows

        Pt = _cast_keep_scales(params, compute_dtype)
        lookup_w, pos_w, blocks, lnf_p, lin_p = \
            _resolve_decode_views(model, m.off, Pt)
        proj, row_proj = _proj_fns(adapter, adapter_ids, bank, mesh,
                                   model_axis)
        N = tokens.shape[0]
        start = carry["pos"]
        new_carry = dict(carry)
        # per-layer fp32 chunk K/V: the int8 path's commit waits for
        # acceptance, so nothing a rejected draft produced can reach
        # the carry
        chunk_kv = [] if kv_quant else None
        pos_rows, attend, rows, qpos = _window_view(
            new_carry, lengths, S, max_len, m.scale, m.cache_dtype,
            kv_quant, deferred=chunk_kv)
        x = _embed(lookup_w, pos_w, tokens, pos_rows)     # (N, S, Hid)
        for i, (blk, bp) in enumerate(blocks):
            x = _block(blk, bp, i, x, proj, row_proj, attend)
        # EVERY position's next-token distribution (the whole point —
        # prefill keeps only the last valid one): (N, S, V)
        logp = _log_probs(_head(m.lnf, lnf_p, lin_p, x))
        # sequential per-position sampling through THE one sampler: the
        # lane splits once per position in order, penalty counts grow
        # per draw — position j computes exactly the baseline step's
        # draw for emission j. S is small and static, so the unrolled
        # chain stays one compiled program.
        keys, counts = carry["rng"], carry["tok_counts"]
        pmask = carry["prompt_mask"]
        toks_out, lps_out, key_hist = [], [], []
        active = lengths > 0
        for j in range(S):
            t_j, lp_j, keys, counts = sample_rows(
                logp[:, j], keys, knobs, counts, pmask, active)
            toks_out.append(t_j)
            lps_out.append(lp_j)
            key_hist.append(keys)
        s_tok = jnp.stack(toks_out, axis=1)           # (N, S)
        s_lp = jnp.stack(lps_out, axis=1)
        # acceptance chain: position j's draw is emitted iff every draft
        # before it matched its preceding draw (cumulative product of
        # leading matches); the first mismatch still emits — its draw
        # conditioned on the true accepted context
        if S > 1:
            match = s_tok[:, :-1] == tokens[:, 1:]
            has_draft = jnp.arange(1, S)[None] < lengths[:, None]
            acc = jnp.cumprod((match & has_draft).astype(jnp.int32),
                              axis=1)
            n_acc = jnp.sum(acc, axis=1)
        else:
            n_acc = jnp.zeros((N,), jnp.int32)
        n_emit = jnp.where(active, n_acc + 1, 0).astype(jnp.int32)
        if kv_quant:
            # the DEFERRED accepted-only int8 commit: amax over emitted
            # columns alone, grow-only merge, quantized scatter of
            # exactly those columns (rejected drafts leave scales AND
            # stored bytes bitwise untouched — inactive rows write
            # nothing, amax 0, so their scales pass through bitwise
            # like every other write path's inactive rows). One
            # unconditional full-row requant per layer — the same cost
            # the in-loop merge paid before the restructure.
            emit = jnp.arange(S)[None] < n_emit[:, None]      # (N, S)
            emitf = emit[:, :, None, None]
            widx_e = jnp.where(emit, qpos, max_len)
            for i, (k32, v32) in enumerate(chunk_kv):
                k_amax = jnp.max(jnp.abs(k32) * emitf, axis=(1, 3))
                v_amax = jnp.max(jnp.abs(v32) * emitf, axis=(1, 3))
                kc_rq, ks_new, ks_safe = _kv_quant_merge(
                    new_carry[f"k{i}"], new_carry[f"k{i}_scale"], k_amax)
                vc_rq, vs_new, vs_safe = _kv_quant_merge(
                    new_carry[f"v{i}"], new_carry[f"v{i}_scale"], v_amax)
                new_carry[f"k{i}"] = kc_rq.at[rows[:, None], widx_e].set(
                    _kv_quantize(k32, ks_safe[:, None, :, None]
                                 ).reshape(N, S, -1), mode="drop")
                new_carry[f"v{i}"] = vc_rq.at[rows[:, None], widx_e].set(
                    _kv_quantize(v32, vs_safe[:, None, :, None]
                                 ).reshape(N, S, -1), mode="drop")
                new_carry[f"k{i}_scale"] = ks_new
                new_carry[f"v{i}_scale"] = vs_new
        # lane/counts advance by EXACTLY n_emit draws. The lane: select
        # the key after the last emitted draw from the (S, N, 2) split
        # history (inactive rows stay bitwise untouched). The counts:
        # sample_rows adds exactly one_hot(draw) per call, so the state
        # after n_emit draws is counts0 + the emitted draws' one-hots —
        # S small scatters instead of materializing an (S, N, vocab)
        # history stack on the decode hot path (unemitted/inactive rows
        # add 0, staying bitwise untouched)
        kh = jnp.stack(key_hist)                      # (S, N, 2)
        idx = jnp.clip(n_emit - 1, 0, S - 1)
        new_carry["rng"] = jnp.where(active[:, None], kh[idx, rows],
                                     carry["rng"])
        new_counts = carry["tok_counts"]
        for j in range(S):
            new_counts = new_counts.at[rows, s_tok[:, j]].add(
                (j < n_emit).astype(jnp.int32))
        new_carry["tok_counts"] = new_counts
        # accepted-prefix rollback: pos advances by the emitted count
        # only — chunk writes past it are stale bytes behind the mask
        new_carry["pos"] = start + n_emit
        return s_tok, s_lp, n_emit, new_carry

    fn = verify
    if mesh is not None:
        fn = _shard_step(
            fn, model, mesh, data_axis, model_axis,
            serving_carry_specs(model, sampling=True, data_axis=data_axis,
                                model_axis=model_axis, kv_quant=kv_quant),
            n_out=3, knobs=True, adapter=adapter)
    # carry donated like the decode step's: the engine swaps its pooled
    # carry for the output every super-step
    return jax.jit(fn, donate_argnums=(3,)), init_carry


# -- jitted-step cache (ADVICE r5: generate()/beam_generate() paid two
# full XLA compiles per call; the serving engine shares the same cache) --

import weakref as _weakref

_SERVING_STEPS: dict = {}          # id(model) -> {(kind, dtype): step}


def _step_cache(model: Sequential, kind: str, compute_dtype, builder,
                extra=None):
    """Per-(model, kind, compute_dtype[, extra]) cache of built serving
    steps. ``extra`` extends the key for mesh-lowered variants (a
    ``jax.sharding.Mesh`` hashes by device assignment + axis names, so
    two engines over the same mesh share one compiled program while
    different mesh shapes stay distinct).

    Keyed by ``id(model)`` with a ``weakref.finalize`` that drops the
    entry when the model is collected (a dropped model frees its
    compiled steps; a WeakKeyDictionary could NOT — the cached step
    closures strongly reference the model, so weak keys would never
    die). Dtype is keyed by name. Prompt-length buckets need no
    explicit key: the cached prefill wrapper is ONE ``jax.jit`` whose
    internal trace cache is keyed on argument shapes, so each (B, P)
    bucket compiles once and is reused across calls. The cache assumes
    the model's ARCHITECTURE is frozen after first use (the steps bake
    structure, not weights — weights ride as runtime arguments)."""
    import numpy as np

    mid = id(model)
    per_model = _SERVING_STEPS.get(mid)
    if per_model is None:
        per_model = _SERVING_STEPS[mid] = {}
        # pops the entry at gc, so a recycled id() starts fresh
        _weakref.finalize(model, _SERVING_STEPS.pop, mid, None)
    key = (kind,
           None if compute_dtype is None else np.dtype(compute_dtype).name,
           extra)
    if key not in per_model:
        per_model[key] = builder()
    return per_model[key]


def get_decode_step(model: Sequential, compute_dtype=None):
    """Cached :func:`make_decode_step` — same ``(step, init_carry)``
    tuple for repeated calls with the same (model, compute_dtype)."""
    return _step_cache(model, "decode", compute_dtype,
                       lambda: make_decode_step(model, compute_dtype))


def get_prefill_step(model: Sequential, compute_dtype=None,
                     kv_quant: bool = False):
    """Cached :func:`make_prefill_step` (one wrapper; jit re-traces per
    prompt-length bucket internally and caches each compilation).
    ``kv_quant`` selects the int8-KV-writing variant (own cache
    entry — the carries have different structures)."""
    return _step_cache(model, "prefill", compute_dtype,
                       lambda: make_prefill_step(model, compute_dtype,
                                                 kv_quant=kv_quant),
                       extra="int8" if kv_quant else None)


def get_batch_decode_step(model: Sequential, compute_dtype=None,
                          sampling: bool = False, mesh=None,
                          data_axis: str = "data",
                          model_axis: Optional[str] = "model",
                          kv_quant: bool = False, adapter=None):
    """Cached :func:`make_batch_decode_step` (the serving engine's step).
    ``sampling=True`` selects the sampled-epilogue variant (its own
    cache entry — the two steps have different signatures/carries);
    ``mesh`` selects the shard_map-lowered tensor-parallel variant
    (cached per mesh); ``kv_quant`` the int8-KV variant (own entry —
    different carry structure); ``adapter`` (a hashable
    :class:`~bigdl_tpu.serving.lora.AdapterSpec`) the multi-tenant
    variant — engines sharing a (model, dtype, adapter-config) share
    one compiled program. See :func:`make_batch_decode_step`."""
    kind = "batch_decode_sample" if sampling else "batch_decode"
    extra = ("int8" if kv_quant else None,
             None if mesh is None else (mesh, data_axis, model_axis),
             adapter)
    return _step_cache(model, kind, compute_dtype,
                       lambda: make_batch_decode_step(
                           model, compute_dtype, sampling=sampling,
                           mesh=mesh, data_axis=data_axis,
                           model_axis=model_axis, kv_quant=kv_quant,
                           adapter=adapter),
                       extra=extra)


def get_batch_verify_step(model: Sequential, compute_dtype=None,
                          width: int = 4, mesh=None,
                          data_axis: str = "data",
                          model_axis: str = "model",
                          kv_quant: bool = False, adapter=None):
    """Cached :func:`make_batch_verify_step` (the speculative engine's
    one target-side program). ``width`` (the chunk width = max drafts
    + 1) keys the cache alongside the mesh/kv_quant/adapter variants —
    engines sharing a (model, dtype, width) share one compiled verify
    program, exactly like the decode step cache."""
    extra = (int(width), "int8" if kv_quant else None,
             None if mesh is None else (mesh, data_axis, model_axis),
             adapter)
    return _step_cache(model, "batch_verify", compute_dtype,
                       lambda: make_batch_verify_step(
                           model, compute_dtype, width=width, mesh=mesh,
                           data_axis=data_axis, model_axis=model_axis,
                           kv_quant=kv_quant, adapter=adapter),
                       extra=extra)


def get_batch_prefill_step(model: Sequential, compute_dtype=None,
                           mesh=None, data_axis: str = "data",
                           model_axis: str = "model",
                           carry_sampling: bool = False,
                           kv_quant: bool = False, adapter=None):
    """Cached :func:`make_batch_prefill_step` (the batched-admission
    prefill; one wrapper whose jit re-traces per (B, L) bucket).
    ``mesh``/``carry_sampling`` select the shard_map-lowered tensor-
    parallel variant (cached per mesh + carry layout); ``kv_quant``
    the int8-KV-writing variant; ``adapter`` the multi-tenant variant
    (prefill signature grows ``(adapter_ids, bank)``)."""
    extra = ("int8" if kv_quant else None,
             None if mesh is None else (mesh, data_axis, model_axis,
                                        carry_sampling),
             adapter)
    return _step_cache(model, "batch_prefill", compute_dtype,
                       lambda: make_batch_prefill_step(
                           model, compute_dtype, mesh=mesh,
                           data_axis=data_axis, model_axis=model_axis,
                           carry_sampling=carry_sampling,
                           kv_quant=kv_quant, adapter=adapter),
                       extra=extra)


def beam_generate(model: Sequential, prompt_ids, beam_size: int = 4,
                  decode_length: int = 32, eos_id: int = -1,
                  alpha: float = 0.6, compute_dtype=None):
    """Beam-search continuation of a prompt with the KV-cached decoder.

    ``prompt_ids``: (P,) 1-based word ids for ONE prompt (decode several
    prompts with separate calls — beam_search's sos is scalar). Returns
    ``(sequences (beam, decode_length) of 1-based ids, scores (beam,))``.
    ``eos_id`` is a 1-based id, or -1 for none. ``compute_dtype``
    (e.g. bf16) selects the serving precision; weights ride as runtime
    arguments either way (large models cannot bake them into the
    program — see :func:`make_decode_step`).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.nn.beam_search import beam_search

    # cached per (model, dtype) — repeated calls stop paying XLA compiles
    step, init_carry = get_decode_step(model, compute_dtype=compute_dtype)
    P = jax.device_put(serving_params(model, compute_dtype))
    prompt = [int(t) for t in prompt_ids]
    assert prompt, "need a non-empty prompt"
    max_len = model.modules[1].max_len
    if len(prompt) - 1 + decode_length > max_len:
        raise ValueError(
            f"prompt ({len(prompt)}) + decode_length ({decode_length}) "
            f"exceeds the model's max_len {max_len} — the cache position "
            "would silently clamp (same guard as PositionEmbedding)")
    K = beam_size
    carry = init_carry(K)
    # prime the cache with the prompt in ONE prefill pass (every beam
    # identical; sequential single-token priming re-reads all weights
    # per prompt token)
    if len(prompt) > 1:
        prefill = get_prefill_step(model, compute_dtype=compute_dtype)
        ptoks = jnp.tile(jnp.asarray([t - 1 for t in prompt[:-1]],
                                     jnp.int32)[None], (K, 1))
        _, carry = prefill(P, ptoks, carry)
    vocab = model.modules[0].n_index
    seqs, scores = beam_search(
        step, P, carry, 1, K, vocab, decode_length,
        sos_id=prompt[-1] - 1,
        eos_id=(eos_id - 1) if eos_id > 0 else vocab + 7,
        alpha=alpha, padding_value=-1)
    out = np.asarray(seqs)[0] + 1            # back to 1-based ids
    return out, np.asarray(scores)[0]


def generate(model: Sequential, prompt_ids, length: int = 32,
             temperature: float = 1.0, top_k: int = 0, seed: int = 0,
             compute_dtype=None, sampling=None, return_logprobs=False):
    """Sampled (or greedy) continuation with the KV-cached decoder.

    ``temperature=0`` is greedy argmax; ``top_k > 0`` restricts sampling
    to the k most likely tokens. Returns (n,) 1-based word ids (n ==
    ``length`` unless a stop set ends the run early);
    ``return_logprobs=True`` returns ``(ids, logprobs)`` with the chosen
    tokens' raw model log-probs. ``compute_dtype`` selects the serving
    precision; weights ride as runtime arguments
    (see :func:`make_decode_step`).

    ``sampling`` takes a full
    :class:`bigdl_tpu.serving.sampling.SamplingParams` (top-p,
    penalties, min/max tokens, stop sets — it overrides the
    ``temperature``/``top_k``/``seed`` scalars). The draw runs through
    the SAME per-row sampler as the serving engine
    (:func:`~bigdl_tpu.serving.sampling.sample_rows` with one row), with
    the lane seeded by the same seed → key rule — so a fixed seed yields
    the engine's token stream for the same request (to the usual float
    round-off caveat on near-tied logits).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bigdl_tpu.serving.sampling import (
        SamplingParams, get_sampler, knob_row_values, lane_key,
        match_stop_sequences,
    )

    sp = sampling if sampling is not None else SamplingParams(
        temperature=temperature, top_k=top_k, seed=seed)
    if sp.max_tokens is not None:
        length = sp.max_tokens
    # cached per (model, dtype) — repeated calls stop paying XLA compiles
    step, init_carry = get_decode_step(model, compute_dtype=compute_dtype)
    P = jax.device_put(serving_params(model, compute_dtype))
    prompt = [int(t) for t in prompt_ids]
    assert prompt, "need a non-empty prompt"
    max_len = model.modules[1].max_len
    if len(prompt) - 1 + length > max_len:
        raise ValueError(
            f"prompt ({len(prompt)}) + length ({length}) exceeds the "
            f"model's max_len {max_len} — the cache position would "
            "silently clamp (same guard as PositionEmbedding)")
    carry = init_carry(1)
    if len(prompt) > 1:
        prefill = get_prefill_step(model, compute_dtype=compute_dtype)
        ptoks = jnp.asarray([[t - 1 for t in prompt[:-1]]], jnp.int32)
        _, carry = prefill(P, ptoks, carry)

    # one-row sampler state: the engine's per-slot layout with N=1
    vocab = model.modules[0].n_index
    scal, ban_row = knob_row_values(sp, -1)
    ban_base = bool(scal["ban"])
    knobs = {k: jnp.asarray([v]) for k, v in scal.items()}
    knobs["ban_ids"] = jnp.asarray(ban_row[None])
    counts = jnp.zeros((1, vocab), jnp.int32)
    pmask = np.zeros((vocab,), bool)
    pmask[np.clip(np.asarray(prompt) - 1, 0, vocab - 1)] = True
    pmask = jnp.asarray(pmask[None])
    keys = lane_key(sp.seed if sp.seed is not None else seed)[None]
    sampler = get_sampler()

    tok = jnp.asarray([prompt[-1] - 1], jnp.int32)
    out, lps = [], []
    # min-tokens ban rides as a runtime VALUE (no retrace); with no ban
    # configured it is the constant False — upload it once, not per token
    knobs["ban"] = jnp.asarray([False])
    for i in range(length):
        logp, carry = step(P, tok, carry)
        if ban_base:
            knobs["ban"] = jnp.asarray([i < sp.min_tokens])
        tok, chosen, keys, counts = sampler(logp, keys, knobs, counts,
                                            pmask)
        t1 = int(tok[0]) + 1                 # back to 1-based ids
        out.append(t1)
        lps.append(float(chosen[0]))
        if len(out) >= sp.min_tokens and (
                t1 in sp.stop_token_ids
                or match_stop_sequences(out, sp.stop_sequences)):
            break
    ids = np.asarray(out, np.int32)
    if return_logprobs:
        return ids, np.asarray(lps, np.float32)
    return ids

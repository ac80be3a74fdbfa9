"""Operations and bytes a kernel's ALGORITHM needs for one training
iteration of a cell, from the cell's shapes, and the least time the chip
could take for them. Kept with the benchmark, like ``flops.py``, so that
no PR that claims a gain can change what a kernel is held against.

Work the algorithm does not need does not count: a causal kernel's
masked-out half counts for nothing, and what the backward pass
recomputes of the forward (the scores, in each of its two kernels)
counts for nothing either. Bytes are each operand read once and each
result written once.
"""

from __future__ import annotations

BF16 = 2            # bytes of an element of q, k, v, o and their gradients
F32 = 4             # of the log-sum-exp and delta rows


def flash_attention(config: dict, settings: dict) -> dict:
    """Causal self-attention of ``batch x heads`` sequences of
    ``seq_len`` x ``d_head``, over all layers, per iteration and chip:
    ``{"fwd": {"flops", "bytes"}, "bwd": {...}}``.

    A (query, key) pair that the mask keeps costs ``2 d`` operations in
    each matrix product it takes part in. Forward: QK^T and PV. Backward:
    dP = dO V^T, dV = P^T dO, dQ = dS K, dK = dS^T Q (the recomputed
    QK^T does not count). Forward reads q, k, v and writes o and the
    log-sum-exp; backward reads q, k, v, dO, the log-sum-exp and delta
    and writes dq, dk, dv."""
    heads, d = config["n_head"], config["n_embd"] // config["n_head"]
    t, layers = settings["seq_len"], config["n_layer"]
    seqs = settings["batch_size"] * heads * layers
    pairs = t * (t + 1) // 2                   # causal: j <= i
    tensor = t * d * BF16
    row = t * F32
    return {
        "fwd": {"flops": seqs * 2 * (2 * d * pairs),
                "bytes": seqs * (4 * tensor + row)},
        "bwd": {"flops": seqs * 4 * (2 * d * pairs),
                "bytes": seqs * (7 * tensor + 2 * row)},
    }


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """(seconds, bound): the larger of operations over the peak rate and
    bytes over the peak bandwidth, and which of the two it was."""
    compute = flops / peaks["bf16_flops"]
    memory = nbytes / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")

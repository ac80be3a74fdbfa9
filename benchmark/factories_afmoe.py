"""How ``configs/trinity-large-preview.json`` becomes the program's
model."""

from __future__ import annotations


def afmoe_lm(config: dict):
    """The ``afmoe`` decoder from its published ``config.json`` keys
    (``num_experts`` the experts held here, ``expert_share`` which of
    the layer's). The cache window and the dtype the parameters are
    created in are the cell's (``serve.max_len``, ``serve.param_dtype``):
    ``ServingEngine`` has no option for either, so the model object
    carries them, as ``TransformerLM(max_len=)`` does."""
    from bigdl_tpu.models.afmoe import AfmoeLM

    serve = config["serve"]
    return AfmoeLM(config, max_len=serve["max_len"],
                   param_dtype=serve["param_dtype"])

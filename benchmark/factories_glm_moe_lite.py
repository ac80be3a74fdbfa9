"""How ``configs/glm-4.7-flash.json`` becomes the program's model."""

from __future__ import annotations


def glm_moe_lite_lm(config: dict):
    """The ``glm4_moe_lite`` decoder from its published ``config.json``
    keys (``n_routed_experts`` the experts held here, ``expert_share``
    which of the layer's). The cache window and the dtype the parameters
    are created in are the cell's (``serve.max_len``,
    ``serve.param_dtype``): ``ServingEngine`` has no option for either,
    so the model object carries them, as ``TransformerLM(max_len=)``
    does."""
    from bigdl_tpu.models.glm_moe_lite import GlmMoeLiteLM

    serve = config["serve"]
    return GlmMoeLiteLM(config, max_len=serve["max_len"],
                        param_dtype=serve["param_dtype"])

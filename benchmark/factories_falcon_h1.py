"""How ``configs/falcon-h1-34b.json`` becomes the program's model."""

from __future__ import annotations


def falcon_h1_lm(config: dict):
    """Falcon-H1 from its published ``config.json`` keys. The cache
    window and the dtype the parameters are created in are the cell's
    (``serve.max_len``, ``serve.param_dtype``): ``ServingEngine`` has no
    option for either, so the model object carries them, as
    ``TransformerLM(max_len=)`` does."""
    from bigdl_tpu.models.falcon_h1 import FalconH1LM

    serve = config["serve"]
    return FalconH1LM(config, max_len=serve["max_len"],
                      param_dtype=serve["param_dtype"])

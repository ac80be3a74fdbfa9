"""How a configuration file's sizes become the program's model: one
small function per family, named by the file as ``module:function``. A
new family adds a module of its own beside this one."""

from __future__ import annotations


def gpt2_lm(config: dict):
    """GPT-2 from its published ``config.json`` keys."""
    from bigdl_tpu.models import TransformerLM

    return TransformerLM(config["vocab_size"], hidden_size=config["n_embd"],
                         n_heads=config["n_head"],
                         n_layers=config["n_layer"],
                         max_len=config["n_positions"], output="logits")


def resnet(config: dict):
    from bigdl_tpu.models import ResNet

    return ResNet(config["num_classes"],
                  {"depth": config["depth"],
                   "shortcutType": config["shortcut_type"]})

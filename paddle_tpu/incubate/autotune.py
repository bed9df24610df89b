"""paddle.incubate.autotune (reference ``python/paddle/incubate/autotune.py``
``set_config`` driving kernel/layout/dataloader autotuning).

TPU-native: kernel selection is XLA's job (its autotuner runs at compile
time) and attention's routes are chosen from shape and platform
(``nn.functional.attention.attention_route``), so ``kernel.enable`` is
accepted and recorded in the status and changes nothing; ``layout.enable``
switches the layout autotuner."""
from __future__ import annotations

import json

__all__ = ["set_config"]

_STATUS = {"kernel": {"enable": True}, "layout": {"enable": False},
           "dataloader": {"enable": False}}


def set_config(config=None):
    """Accepts the reference's dict or a JSON file path."""
    if config is None:
        # reference semantics: config=None resets EVERY autotune section to
        # its default, not just the kernel one
        from ..framework.layout_autotune import enable_layout_autotune

        _STATUS["kernel"]["enable"] = True
        _STATUS["layout"]["enable"] = False
        _STATUS["dataloader"]["enable"] = False
        enable_layout_autotune(False)
        return
    if isinstance(config, str):
        with open(config) as f:
            config = json.load(f)
    if not isinstance(config, dict):
        raise TypeError("set_config expects None, a dict, or a JSON path")
    for key in config:
        if key not in _STATUS:
            raise ValueError(f"unknown autotune section {key!r}")
        section = config[key] or {}
        _STATUS[key].update(section)
    if "layout" in config:
        from ..framework.layout_autotune import enable_layout_autotune

        enable_layout_autotune(bool(_STATUS["layout"].get("enable")))


def get_status():
    return {k: dict(v) for k, v in _STATUS.items()}

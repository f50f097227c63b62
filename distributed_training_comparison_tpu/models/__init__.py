"""Model zoo.

Parity: reference ``src/single/net.py`` (identical copy in all three variant
dirs) — CIFAR-style ResNet-18/34/50/101/152.  Unlike the reference, the
``--model`` flag is live: ``get_model`` resolves any zoo entry (the reference
hardcodes ``ResNet18()`` in every ``main.py`` and leaves the flag dead,
``src/single/main.py:15`` / ``src/single/config.py:23``).
"""

from .resnet import (
    BasicBlock,
    Bottleneck,
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)
from .afmoe import AFMOE_TINY_MODEL, TRINITY_MINI_MODEL, Afmoe
from .lfm2 import LFM2, LFM2_24B_A2B_MODEL, LFM2_TINY_MODEL
from .nemotron_h import NEMOTRON_H_MODEL, NEMOTRON_H_TINY_MODEL, NemotronH
from .qwen3_next import QWEN3_NEXT_MODEL, QWEN3_NEXT_TINY_MODEL, Qwen3Next
from .moe import SwitchFFN, TopKMoE, resolve_dispatch
from .vit import ViT, ViTBlock, ViTLong, ViTMoE, ViTSmall, ViTTiny

_ZOO = {
    "resnet18": ResNet18,
    "resnet34": ResNet34,
    "resnet50": ResNet50,
    "resnet101": ResNet101,
    "resnet152": ResNet152,
    "vit_tiny": ViTTiny,
    "vit_small": ViTSmall,
    "vit_long": ViTLong,
    "vit_moe": ViTMoE,
    "lfm2_24b_a2b": LFM2_24B_A2B_MODEL,
    "lfm2_tiny": LFM2_TINY_MODEL,
    "trinity_mini": TRINITY_MINI_MODEL,
    "afmoe_tiny": AFMOE_TINY_MODEL,
    "qwen3_next": QWEN3_NEXT_MODEL,
    "qwen3_next_tiny": QWEN3_NEXT_TINY_MODEL,
    "nemotron_h": NEMOTRON_H_MODEL,
    "nemotron_h_tiny": NEMOTRON_H_TINY_MODEL,
}


def model_cli_options(name: str) -> tuple:
    """The launcher flags (as ``hparams`` fields) a zoo entry takes beyond
    the keywords every model gets: its constructor's ``cli_options``."""
    return tuple(getattr(_ZOO.get(name.lower()), "cli_options", ()))


def get_model(name: str, *, expert_parallel: bool = False, **kwargs):
    """Build a zoo model by CLI name (e.g. ``"resnet18"``, ``"vit_tiny"``).

    ``expert_parallel=True`` declares that the caller will shard
    expert-stacked parameters over the ``"model"`` mesh axis; the MoE
    dispatch is then resolved sharding-aware at construction (``'auto'``
    falls back to the partitionable ``'gather'``, an explicit ``'gmm'``
    is rejected) — for *every* caller, not just the Trainer
    (``models.moe.resolve_dispatch``).
    """
    try:
        ctor = _ZOO[name.lower()]
    except KeyError:
        raise ValueError(f"unknown model {name!r}; choices: {sorted(_ZOO)}") from None
    if name.lower().startswith("vit"):
        kwargs["moe_dispatch"] = resolve_dispatch(
            kwargs.get("moe_dispatch", "auto"), expert_parallel=expert_parallel
        )
    return ctor(**kwargs)


__all__ = [
    "BasicBlock",
    "Bottleneck",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "ResNet152",
    "ViT",
    "ViTBlock",
    "ViTTiny",
    "ViTSmall",
    "ViTLong",
    "ViTMoE",
    "SwitchFFN",
    "TopKMoE",
    "LFM2",
    "Afmoe",
    "Qwen3Next",
    "NemotronH",
    "get_model",
    "model_cli_options",
    "resolve_dispatch",
]

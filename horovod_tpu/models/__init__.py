"""Model zoo covering the reference's benchmark configs (BASELINE.json):
MNIST CNN, ResNet-50, BERT-large, GPT-2 medium, ViT-B/16 — implemented in
flax for TPU (bf16 compute, MXU-friendly shapes), not ported from the
reference's TF/torch example scripts. Plus the Llama family (RoPE +
RMSNorm + SwiGLU + GQA, optional Mixtral-style MoE) and the T5
encoder-decoder family — all three architecture classes — and five families
trained here and not served, each with routed experts: block diffusion
(``sdar.py``), gated short convolutions beside attention (``lfm2.py``),
latent attention with multi-token prediction (``glm4_moe_lite.py``), window
beside position-free global attention (``smallthinker.py``) and KDA beside
latent attention (``kimi_linear.py``). The decoder parts that more than one
family uses (RMSNorm, RoPE, grouped-query and latent attention, the
next-token head) are ``layers.py``'s; no training family imports another.
"""

from horovod_tpu.models.mnist import MnistCNN  # noqa: F401
from horovod_tpu.models.resnet import ResNet50, ResNet18  # noqa: F401

__all__ = ["MnistCNN", "ResNet50", "ResNet18", "LFM2", "LFM2Config",
           "Glm4MoeLite", "Glm4MoeLiteConfig", "SmallThinker",
           "SmallThinkerConfig", "KimiLinear", "KimiLinearConfig",
           "get_model"]


# flax-heavy families load when they are asked for, as get_model does
_LAZY = {"LFM2": "lfm2", "LFM2Config": "lfm2",
         "Glm4MoeLite": "glm4_moe_lite", "Glm4MoeLiteConfig": "glm4_moe_lite",
         "SmallThinker": "smallthinker", "SmallThinkerConfig": "smallthinker",
         "KimiLinear": "kimi_linear", "KimiLinearConfig": "kimi_linear"}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        return getattr(importlib.import_module(
            f"{__name__}.{_LAZY[name]}"), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def get_model(name: str, **kw):
    name = name.lower()
    if name == "mnist":
        return MnistCNN(**kw)
    if name == "resnet50":
        return ResNet50(**kw)
    if name == "resnet18":
        return ResNet18(**kw)
    if name in ("gpt2", "gpt2_medium", "gpt2-medium"):
        from horovod_tpu.models.gpt2 import GPT2, GPT2Config
        return GPT2(GPT2Config.medium() if "medium" in name else GPT2Config(**kw))
    if name in ("bert", "bert_large", "bert-large"):
        from horovod_tpu.models.bert import Bert, BertConfig
        return Bert(BertConfig.large() if "large" in name else BertConfig(**kw))
    if name in ("vit", "vit_b16", "vit-b/16"):
        from horovod_tpu.models.vit import ViT, ViTConfig
        return ViT(ViTConfig.b16() if name != "vit" else ViTConfig(**kw))
    if name in ("llama", "llama7b", "llama_small"):
        import dataclasses

        from horovod_tpu.models.llama import Llama, LlamaConfig
        # kwargs override fields of the NAMED preset; they never fall back
        # to the raw LlamaConfig defaults (the 7B shape — too big to init
        # casually on a host or single chip).
        base = (LlamaConfig.llama7b() if name == "llama7b"
                else LlamaConfig.small())
        return Llama(dataclasses.replace(base, **kw) if kw else base)
    if name in ("t5", "t5_small", "t5-small"):
        import dataclasses

        from horovod_tpu.models.t5 import T5, T5Config
        base = T5Config.small() if "small" in name else T5Config()
        return T5(dataclasses.replace(base, **kw) if kw else base)
    raise ValueError(f"unknown model {name}")

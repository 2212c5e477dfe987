"""Model zoo covering the reference's benchmark configs (BASELINE.json):
MNIST CNN, ResNet-50, BERT-large, GPT-2 medium, ViT-B/16 — implemented in
flax for TPU (bf16 compute, MXU-friendly shapes), not ported from the
reference's TF/torch example scripts. Plus the Llama family (RoPE +
RMSNorm + SwiGLU + GQA, optional Mixtral-style MoE) and the T5
encoder-decoder family for modern-LLM migrations — all three
architecture classes (decoder-only, encoder-only, encoder-decoder) — and a
block-diffusion decoder with dropless routed experts (``models/sdar.py``,
training only), and a hybrid of gated short convolutions and grouped-query
attention with a dense SwiGLU first and bias-selected routed experts after
(``models/lfm2.py``, training only), and a decoder with latent attention, a
shared expert beside the routed ones and a multi-token-prediction module in
its loss (``models/glm4_moe_lite.py``, training only), and a decoder of
position-free global attention beside sliding-window attention with RoPE,
whose router reads a block's input before attention and whose experts are
ReGLU (``models/smallthinker.py``, training only).
"""

from horovod_tpu.models.mnist import MnistCNN  # noqa: F401
from horovod_tpu.models.resnet import ResNet50, ResNet18  # noqa: F401

__all__ = ["MnistCNN", "ResNet50", "ResNet18", "LFM2", "LFM2Config",
           "Glm4MoeLite", "Glm4MoeLiteConfig", "SmallThinker",
           "SmallThinkerConfig", "get_model"]


def __getattr__(name):
    # flax-heavy families load when they are asked for, as get_model does
    if name in ("LFM2", "LFM2Config"):
        from horovod_tpu.models import lfm2
        return getattr(lfm2, name)
    if name in ("Glm4MoeLite", "Glm4MoeLiteConfig"):
        from horovod_tpu.models import glm4_moe_lite
        return getattr(glm4_moe_lite, name)
    if name in ("SmallThinker", "SmallThinkerConfig"):
        from horovod_tpu.models import smallthinker
        return getattr(smallthinker, name)
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

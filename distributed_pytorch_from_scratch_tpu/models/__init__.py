"""The model families, and the one place a family's name becomes a class:
`llama` (the reference's block) and `gpt2`, and thirteen drawn from published
configurations: ten that each hold one share of the experts their router
scores, `mla_moe`, `gdn_moe`, `conv_moe`, `bd_moe`, `swa_moe`, `early_moe`,
`mhc_mla_moe`, `kda_mla_moe`, `ssm_moe` and `dsa_moe` (whose every layer
chooses its keys), and three dense ones:
`loop_llama`, whose stack is passed several times a step, `ssm_dense`,
whose every layer is a Mamba-2 mixer or an attention and then a SwiGLU,
and `sambay`, a decoder-hybrid-decoder whose upper layers read ONE lower
layer's scan output and ONE lower layer's keys and values
(docs/DESIGN.md, "What a family file holds")."""

from .bd_moe import BlockDiffusionMoETransformer
from .conv_moe import ConvMoETransformer
from .dsa_moe import SelectedAttentionMoETransformer
from .early_moe import EarlyRouterMoETransformer
from .gdn_moe import GdnMoETransformer
from .gpt2 import GPT2Transformer
from .kda_mla_moe import KdaMlaMoETransformer
from .loop_llama import LoopedTransformer
from .mhc_mla_moe import HyperLatentMoETransformer
from .mla_moe import LatentMoETransformer
from .sambay import SambaYTransformer
from .ssm_dense import SsmDenseTransformer
from .ssm_moe import SsmMoETransformer
from .stack import DecoderStack
from .swa_moe import SlidingWindowMoETransformer
from .transformer import Transformer

FAMILIES = {cls.family: cls for cls in (
    Transformer, GPT2Transformer, LatentMoETransformer, GdnMoETransformer,
    ConvMoETransformer, BlockDiffusionMoETransformer,
    SlidingWindowMoETransformer, EarlyRouterMoETransformer,
    HyperLatentMoETransformer, KdaMlaMoETransformer, SsmMoETransformer,
    LoopedTransformer, SsmDenseTransformer,
    SelectedAttentionMoETransformer, SambaYTransformer)}


def family_class(family: str) -> "type[DecoderStack]":
    if family not in FAMILIES:
        raise ValueError(f"unknown model family {family!r}; expected one of "
                         f"{sorted(FAMILIES)}")
    return FAMILIES[family]


def facts_family(cfg) -> "type[DecoderStack]":
    """The family whose `config_extra` is `cfg.family_facts`; the stack
    itself where there are none (llama and gpt2 share those)."""
    if cfg.family_facts is None:
        return DecoderStack
    # (where two families read one field, each says whose the facts are)
    return next(cls for cls in FAMILIES.values()
                if cls.config_extra == cfg.family_facts
                and cls.owns_facts(cfg))


def build_model(family: str, cfg, **kw) -> DecoderStack:
    """`cfg` built as `family` (a key of FAMILIES); `kw` are the stack's
    fields (`DecoderStack`), the same for every family."""
    return family_class(family)(cfg, **kw)

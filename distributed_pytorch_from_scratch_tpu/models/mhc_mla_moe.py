"""The `mhc_mla_moe` family: the `mla_moe` family's block (latent attention,
the sigmoid-routed expert FFN with a shared expert, leading dense layers, the
multi-token-prediction module; models/mla_moe.py) with its residual path
replaced by manifold-constrained hyper-connections (mHC, arXiv:2512.24880):
the architecture `Xing4.0-29B-A4B`'s `config.json` describes (`model_type`
`xing4_0`: DeepSeek-V3's keys plus `hc_mult`, `hc_sinkhorn_iters`, `hc_eps`,
`mhc_h_res_clamp_min/max`), with YaRN positions.

`HyperLatentMoETransformer` is a subclass of `LatentMoETransformer` and
holds only what differs:

* **the residual state is `hc_mult` streams**: ONE declared fact,
  `stream_mixer` (`parallel/hyper.StreamMixer`, made of
  `cfg.latent_moe.hyper`), which the stack reads at a layer's two joints,
  at the embedding and at the head (`DecoderStack.stream_mixer` says how);
  every sublayer F is exactly `mla_moe`'s, reading `sum_i pre_i X[i]`;
* the multi-token-prediction module over streams is `mla_moe`'s own
  `_extra_loss`: one `hnorm` and one projection, stream by stream, a layer
  with its own two mixers, an exit mixer of its own;
* YaRN is `mla_moe`'s too (`cfg.latent_moe.rope_scaling`);
* **its counts**: the mixers' parameters and products (`param_counts`,
  `flops_per_step`) and what a layer's backward holds of the streams beside
  `mla_moe`'s tensors (`layer_extra_elems_per_token`; the kept layer input
  is `residual_streams` x d wide, which `training/memory.py` asks the stack).

What `mla_moe` refuses this family refuses; the stack refuses the pipeline
for any family with streams. Decode and serving over streams are not
written (`decodable` is False, as `mla_moe`'s).

Named scopes inside the step, beside `mla_moe`'s: `mhc` around every mixer
with `mhc/maps`, `mhc/sinkhorn`, `mhc/pre`, `mhc/post`, `mhc/exit` beneath
(parallel/hyper.py; on a TPU the passes over the streams are the Pallas
kernels of ops/pallas/stream_mixer.py, under the same scopes). Counters, one row a layer (dense layers too):
`hc_sinkhorn_err`, `hc_colsum_err`, `hc_res_offdiag`.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Dict

from ..config import ModelConfig
from ..parallel.hyper import StreamMixer
from .mla_moe import LatentMoETransformer


def _mixer(cfg: ModelConfig) -> StreamMixer:
    lm = cfg.latent_moe
    return StreamMixer(
        cfg.attn_dim, lm.hyper.hc_mult, lm.hyper.hc_sinkhorn_iters,
        lm.hyper.hc_eps, lm.rms_norm_eps, lm.hyper.mhc_h_res_clamp_min,
        lm.hyper.mhc_h_res_clamp_max)


@dataclass(frozen=True)
class HyperLatentMoETransformer(LatentMoETransformer):
    """The mhc_mla_moe family (module docstring)."""

    family = "mhc_mla_moe"

    @functools.cached_property
    def stream_mixer(self) -> "StreamMixer | None":
        # (None without the facts: `_check_facts` then says what is missing)
        return _mixer(self.cfg) if self.cfg.latent_moe.hyper else None

    held_beyond_d = 0.0         # the streams' term below stands in for it

    @property
    def layer_extra_elems_per_token(self) -> float:
        """`mla_moe`'s attention and chunk, and of the streams what the
        chip counts of one mixer's backward beside the kept layer input:
        2.02 n d a token (the streams before and after a joint; the
        kernels of ops/pallas/stream_mixer.py, PR 58, hold no float32 copy
        and no second cotangent in HBM: 6 n d read 4.5% over). SET FROM THE
        CHIP'S READING in place of `mla_moe`'s `held_beyond_d`: cell 11
        counts 13.700 GiB at rung `true` and 14.011 at `flash`, the rung
        `auto` picks, for steps this makes 13.88 and 14.18 (ledger, PR 61;
        my chip runs, PR 62). The kept layer inputs themselves are the
        stacks' (`residual_streams`)."""
        return (super().layer_extra_elems_per_token
                + 2.02 * self.residual_streams * self.d)

    @staticmethod
    def param_counts(cfg: ModelConfig) -> Dict[str, int]:
        """`mla_moe`'s parts, and the mixers: two a layer (the module's
        layer too) and an exit mixer behind the model and behind the
        module."""
        lm = cfg.latent_moe
        mixer = _mixer(cfg)
        leave = dataclasses.replace(mixer, exit_only=True).num_params()
        return {**LatentMoETransformer.param_counts(cfg),
                "stream_mixers": (
                    2 * (cfg.num_layers + lm.num_nextn_predict_layers)
                    * mixer.num_params()
                    + (1 + lm.num_nextn_predict_layers) * leave)}

    @staticmethod
    def flops_per_step(cfg, batch, seqlen, num_params) -> float:
        """`mla_moe`'s count: the mixers' maps are products with their W
        (in `num_params`); what the two weighted sums of a mixer add, 2 n d
        and 2 (n + 1) n d a token forward, is counted with them."""
        lm = cfg.latent_moe
        n, d = lm.hyper.hc_mult, cfg.attn_dim
        mixers = 2 * (cfg.num_layers + lm.num_nextn_predict_layers)
        sums = mixers * (2 * n * d + 2 * (n + 1) * n * d)
        return (LatentMoETransformer.flops_per_step(cfg, batch, seqlen,
                                                    num_params)
                + 3 * sums * batch * seqlen)

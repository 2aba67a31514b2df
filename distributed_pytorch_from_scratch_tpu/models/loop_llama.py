"""The `loop_llama` family: the llama block (`models/transformer.py`) whose
STACK IS RUN SEVERAL TIMES A STEP OVER THE SAME WEIGHTS, with an exit at the
end of every pass and a learned exit gate that weighs the exits' losses
(Ouro's architecture, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741), on the same decoder stack as the other families.

`LoopedTransformer` is a subclass of `models/stack.DecoderStack`, as every
family with facts of its own is (the stack builds its tree, its head and its
count from its declarations: tests/test_model_families.py), whose layer is
made of `models/transformer.Transformer`'s OWN modules, so its cell guards
the llama block. It holds only what differs:

* **the loop is the stack's** (`DecoderStack.loop_steps`, declared here by
  `passes(cfg)` = `cfg.loop_llama.loop_steps`, Ouro's `total_ut_steps`): `h_0 = Emb(ids)`,
  `h_r = N_f(Layers(h_{r-1}))` for r = 1..R over the SAME `params["layers"]`,
  the one final norm after every pass; an exit a pass through the one head,
  `l_r[i]` its per-token CE; the exit gate `lam_r[i] = sigmoid(w_g . h_r[i]
  + b_g)` (`params["exit_gate"]`, d + 1 float32 parameters, replicated),
  `p_r = lam_r prod_{j<r} (1 - lam_j)` with the last pass taking what is
  left; `loss = mean_i [sum_r p_r[i] l_r[i] - beta H(p[i])]`, beta
  `exit_entropy_coef` (`DecoderStack._loop_passes`, `_loop_loss`);
* **four norms a layer**: `a = x + N2(Attn(N1(x)))`, `y = a + N4(SwiGLU(
  N3(a)))` (`post_attn_norm_key`, `post_ffn_norm_key`), RMSNorms at
  `rms_norm_eps`;
* **no bias anywhere** (llama's linears carry the reference's), so the
  modules are llama's (`Transformer._mods`) with `add_bias=False` and the
  head the stack's;
* RoPE in the rotate-half convention over the whole head, computed from
  the positions (the stack's `_positions`: no table caps the context);
* the SwiGLU (the stack's `_mlp`, with the ladder's names `ffn_gate` /
  `ffn_up`) runs under the named scope `dense_ffn`;
* its counts: a step's matmul FLOPs are R times what the parameters say.

What it does not run (`refuses`, `decodable`, `hand_reduced_grads`): a
pipeline (its stages would be passed R times a step: a circular schedule),
decode and serving (R caches a layer and an early exit), context
parallelism, a real length under the bucket, and the hand-reduced gradient
builders of training/zero.py (ZeRO 2 / 3, the bucketed reducer), which take
`jax.grad` of a per-shard loss with the varying-axes check off: the R-fold
weight gradient and the gate's are held to the reference on the default
path only. tp, dp and ZeRO-1 are llama's.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict

import jax

from ..config import ModelConfig
from ..parallel.norm import RMSNorm
from .stack import DecoderStack, Params, TPSublayers
from .transformer import Transformer


@dataclass(frozen=True)
class LoopedTransformer(DecoderStack):
    """The loop_llama family (module docstring)."""

    family = "loop_llama"
    ffn_inputs = 2            # gate and up both read the MLP's input
    tied_head = False
    config_extra = "loop_llama"
    post_attn_norm_key = "post_attn_norm"
    post_ffn_norm_key = "post_ffn_norm"
    decodable = False
    hand_reduced_grads = False
    refuses = {
        "pp_size > 1": "a pipeline whose stages a micro-batch passes R "
                       "times a step is a circular schedule; the two "
                       "schedules here pass once",
        "cp_size > 1": "the exits weigh whole rows of a sequence; the "
                       "sequence's shards over 'cp' are not joined there",
        "attn_t_real": "the exits and the gate take every row",
        "ZeRO stage 3": "",
    }

    def _check_facts(self):
        if self.cfg.num_experts:
            raise ValueError("the loop_llama family's layers are dense: "
                             "cfg.num_experts must be 0")

    @staticmethod
    def passes(cfg: ModelConfig) -> int:
        return cfg.loop_llama.loop_steps

    @property
    def exit_entropy_coef(self) -> float:
        return self.cfg.loop_llama.exit_entropy_coef

    # ---- sub-module definitions: llama's, with no bias and four norms ----

    @functools.cached_property
    def _mods(self) -> Dict[str, Any]:
        eps = self.cfg.loop_llama.rms_norm_eps
        # (the llama family's own definition, read off its class: the
        # column- and row-linears with their overlap, the two norms)
        mods = {name: (RMSNorm(self.d, eps) if isinstance(mod, RMSNorm)
                       else dataclasses.replace(mod, add_bias=False))
                for name, mod in Transformer._mods.func(self).items()}
        return {**mods, "post_attn_norm": RMSNorm(self.d, eps),
                "post_ffn_norm": RMSNorm(self.d, eps)}

    def _mlp(self, lp: Params, y: jax.Array, tp: TPSublayers,
             dtype) -> jax.Array:
        with jax.named_scope("dense_ffn"):
            return super()._mlp(lp, y, tp, dtype)

    # ---- counts ----

    @staticmethod
    def param_counts(cfg: ModelConfig) -> Dict[str, int]:
        """The family's parameters by part (`DecoderStack.num_params`)."""
        d, f = cfg.attn_dim, cfg.ffn_dim
        layer = 2 * d * d + 2 * d * cfg.kv_dim + 3 * d * f + 4 * d
        return {"embedding_and_head": 2 * cfg.vocab_size * d,
                "layers": cfg.num_layers * layer, "final_norm": d,
                "exit_gate": d + 1}

    @staticmethod
    def flops_per_step(cfg, batch, seqlen, num_params) -> float:
        """R passes a step: every layer's matrices and the head are used R
        times (the embedding's lookup is no matmul; the norms and the gate
        are no matmuls either), and attention runs R x L times, at the full
        T^2 as every other family counts it (12 H T head_dim a token and
        application: twice the causal triangle)."""
        d = cfg.attn_dim
        matmul = num_params - cfg.vocab_size * d - (
            cfg.num_layers * 4 * d + d + d + 1)
        R = cfg.loop_llama.loop_steps
        return R * (6.0 * matmul * batch * seqlen
                    + 12.0 * cfg.num_layers * batch * cfg.num_heads
                    * seqlen * seqlen * cfg.head_dim)

"""The seam between the decoder stack (`models/stack.DecoderStack`) and the
families that run on it (`models.FAMILIES`): every family states its own
facts and they agree with the tree it builds, a family's name becomes a class
in one place, and no family regrows a copy of what the stack owns."""

import pathlib
import re

import jax
import pytest

from distributed_pytorch_from_scratch_tpu.config import (BdMoEConfig,
                                                         ConvMoEConfig,
                                                         GdnMoEConfig,
                                                         LatentMoEConfig,
                                                         ModelConfig)
from distributed_pytorch_from_scratch_tpu.models import (FAMILIES,
                                                         DecoderStack,
                                                         build_model)
from distributed_pytorch_from_scratch_tpu.training import memory

ROOT = pathlib.Path(__file__).resolve().parents[1]
TINY = dict(attn_dim=32, ffn_dim=64, num_heads=4, num_layers=4,
            vocab_size=96, maxlen=64)
CONFIGS = {"dense": ModelConfig(**TINY),
           "moe8": ModelConfig(num_experts=8, **TINY)}
# a family that reads a config field of its own (`config_extra`) gets it:
# the mla_moe family always has experts; "dense" holds all eight of them,
# "moe8" a share of four, both behind one dense layer and with the module
LATENT = dict(q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
              qk_rope_head_dim=4, v_head_dim=8, moe_intermediate_size=16,
              num_nextn_predict_layers=1)


# the gdn_moe family too: one period of four layers, 4 query heads over 2
# key-value heads, all eight experts held ("dense") or a share of four
GDN = dict(head_dim=16, linear_num_key_heads=2, linear_num_value_heads=4,
           linear_key_head_dim=8, linear_value_head_dim=8,
           moe_intermediate_size=16, shared_expert_intermediate_size=16)


# the conv_moe family: one dense convolution layer (a segment), then one
# period of (attention, conv, conv)
CONV = dict(layer_types=("conv", "full_attention", "conv", "conv"),
            moe_intermediate_size=16, num_dense_layers=1)


# the bd_moe family: 4 query heads over 2 key-value heads of 16 (heads x
# width = 64, not the model's 32), every layer an expert layer
BD = dict(head_dim=16, moe_intermediate_size=16)


def config_for(family, config):
    extra = FAMILIES[family].config_extra
    held = None if config == "dense" else 4
    if extra == "bd_moe":
        return ModelConfig(num_experts=8, num_kv_heads=2, **TINY,
                           bd_moe=BdMoEConfig(experts_held=held, **BD))
    if extra == "conv_moe":
        return ModelConfig(num_experts=8, num_kv_heads=2, **TINY,
                           conv_moe=ConvMoEConfig(experts_held=held, **CONV))
    if extra == "gdn_moe":
        return ModelConfig(num_experts=8, num_kv_heads=2, **TINY,
                           gdn_moe=GdnMoEConfig(experts_held=held, **GDN))
    if extra != "latent_moe":
        return CONFIGS[config]
    return ModelConfig(num_experts=8, **TINY, latent_moe=LatentMoEConfig(
        experts_held=held, **LATENT))


families = pytest.mark.parametrize("family", sorted(FAMILIES))
configs = pytest.mark.parametrize("config", sorted(CONFIGS))


def _shapes(model):
    return jax.eval_shape(model.init, jax.random.key(0))


@families
@configs
@pytest.mark.parametrize("kw", [
    dict(tp_size=2),
    dict(tp_size=2, pp_size=2, pp_schedule="interleaved", pp_virtual=2,
         pp_microbatches=2)], ids=["tp2", "pp2-interleaved"])
def test_init_and_specs_have_the_same_tree(family, config, kw):
    cfg = config_for(family, config)
    if kw.get("pp_size", 1) > 1 and cfg.family_facts is not None:
        # a family with a layer pattern says so where it is built
        with pytest.raises(ValueError, match="pp_size > 1"):
            build_model(family, cfg, **kw)
        return
    model = build_model(family, cfg, **kw)
    params, specs = _shapes(model), model.specs()
    is_spec = lambda x: isinstance(x, jax.sharding.PartitionSpec)
    assert (jax.tree.structure(params)
            == jax.tree.structure(specs, is_leaf=is_spec))
    # one spec entry per array dimension
    jax.tree.map(lambda p, s: pytest.fail(f"{p.shape} vs {s}")
                 if len(s) != p.ndim else None, params, specs)


@families
@configs
def test_num_params_is_the_leaf_count_of_init(family, config):
    cfg = config_for(family, config)
    leaves = jax.tree.leaves(_shapes(build_model(family, cfg)))
    assert FAMILIES[family].num_params(cfg) == sum(x.size for x in leaves)


@families
def test_declared_facts_agree_with_the_tree(family):
    cls = FAMILIES[family]
    model = build_model(family, config_for(family, "dense"))
    params = _shapes(model)
    mlp_inputs = [{"gate_proj", "up_proj"}, {"fc"}]
    # the dense MLP of a family with a layer pattern sits in ONE segment
    reads_input = [names for names in mlp_inputs
                   if any(names <= set(params[key])
                          for key in model._layer_keys)]
    # (a family with no dense MLP at all says 0 and has neither)
    assert [len(names) for names in reads_input] == (
        [cls.ffn_inputs] if cls.ffn_inputs else [])
    assert ("lm_head" not in params) == cls.tied_head
    assert ("pos_embedding" not in params) == cls.uses_rope
    # the decoder reads the projections by these names; a family whose
    # attention is another says it cannot be decoded
    projections = ("wq", "wk", "wv") if cls.decodable else ()
    for key in (cls.attn_norm_key, cls.ffn_norm_key, *projections):
        assert all(key in params[seg] for seg in model._layer_keys)
    # a layer goes through the stack's (q, k, v) dispatch exactly where its
    # parameters hold the stack's output projection (a mixer that hands
    # back its own output keeps its projections inside its module); a
    # family that can be decoded has no other kind of layer
    stacks = [("wo" in params[seg]) for seg in model._layer_keys]
    assert all(stacks) or not cls.decodable
    assert all(("wq" in params[seg]) == ("wo" in params[seg])
               or "wq_a" in params[seg] for seg in model._layer_keys)


def test_build_model_refuses_an_unknown_name_with_the_known_ones():
    with pytest.raises(ValueError) as e:
        build_model("gptj", CONFIGS["dense"])
    for name in FAMILIES:
        assert name in str(e.value)
    with pytest.raises(ValueError, match="unknown model family"):
        memory.estimate_step_gib(CONFIGS["dense"], 4, 64, "true",
                                 family="gptj")


# what the stack owns: a family that defines one of these has regrown a copy
STACK_OWNS = ("tp_layout", "_resolved", "_linear_overlap",
              "_tp_sublayers", "_t_real", "_layer_body", "_forward_with_aux",
              "forward_shard", "_pipeline_layers", "_pipeline_interleaved",
              "_pp_vary_axes", "_live_gated_ring", "to_canonical",
              "from_canonical", "canonical_specs", "_token_ce", "loss_shard",
              "doc_loss_shard", "make_forward", "make_loss", "make_doc_loss",
              "shardings")


@families
@pytest.mark.parametrize("name", STACK_OWNS)
def test_family_resolves_to_the_stacks_function(family, name):
    cls = FAMILIES[family]
    assert issubclass(cls, DecoderStack)
    assert getattr(cls, name) is getattr(DecoderStack, name)


@families
def test_family_adds_no_field_to_the_stacks(family):
    import dataclasses
    names = lambda c: [f.name for f in dataclasses.fields(c)]
    assert names(FAMILIES[family]) == names(DecoderStack)


def _program_sources():
    pkg = ROOT / "distributed_pytorch_from_scratch_tpu"
    files = [p for p in pkg.rglob("*.py") if pkg / "models" not in p.parents]
    files += sorted((ROOT / "scripts").glob("*.py"))
    files += [ROOT / n for n in ("bench.py", "chip_smoke.py",
                                 "__graft_entry__.py")]
    return files


@pytest.mark.parametrize("pattern,why", [
    (r"GPT2Transformer\(", "build a family through models.build_model"),
    (r"family\s*[!=]=", "ask the family's class (models.family_class), "
                        "do not compare its name"),
], ids=["constructs-gpt2", "compares-family-name"])
def test_no_program_file_outside_models_decides_by_family(pattern, why):
    hits = [f"{p.relative_to(ROOT)}:{i}: {line.strip()}"
            for p in _program_sources()
            for i, line in enumerate(p.read_text().splitlines(), 1)
            if re.search(pattern, line)]
    assert not hits, f"{why}:\n" + "\n".join(hits)


def test_memory_does_not_infer_the_mlp_from_the_positions():
    """`uses_rope` says how positions enter (the decoders' hook) and nothing
    else: the MLP's kind is the family's `ffn_inputs`."""
    source = (ROOT / "distributed_pytorch_from_scratch_tpu" / "training"
              / "memory.py").read_text()
    assert "uses_rope" not in source and "ffn_inputs" in source


# The rung `remat="auto"` picks is what `ffn_inputs` and `num_params` feed
# (PR 26's gain in both cells): the estimates and picks below are the
# parent's, taken before the family's facts moved onto its class.
V5E_GIB = 15.75
MEDIUM = ModelConfig(attn_dim=1024, ffn_dim=4096, num_heads=16, num_layers=24,
                     vocab_size=50257, maxlen=1024, compute_dtype="bfloat16")
LARGE = ModelConfig(attn_dim=1280, ffn_dim=5120, num_heads=20, num_layers=36,
                    vocab_size=50257, maxlen=1024, compute_dtype="bfloat16")
PINNED = {
    # shape: (cfg, batch, seqlen, layout, picked, {rung: GiB})
    "gpt2-medium.train-b12-t1024": (
        MEDIUM, 12, 1024, dict(family="gpt2"), "ffn",
        {"true": 8.54388427734375, "attn_proj": 8.54388427734375,
         "ffn": 10.79388427734375, "flash": 11.37396240234375,
         "dots": 13.06146240234375, "false": 19.24896240234375}),
    "gpt2-large.train-dp2-tp2": (
        LARGE, 16, 1024, dict(family="gpt2", tp=2, world=4, dp=2,
                              sequence_parallel=True), "dots",
        {"true": 6.970902919769287, "attn_proj": 7.322465419769287,
         "ffn": 8.728715419769287, "flash": 9.091264247894287,
         "dots": 10.145951747894287, "false": 13.661576747894287}),
    "llama-45m-b32-t1000": (
        ModelConfig(compute_dtype="bfloat16"), 32, 1000,
        dict(family="llama"), "false",
        {"true": 1.825301170349121, "attn_proj": 1.825301170349121,
         "ffn": 4.754988670349121, "flash": 5.132643699645996,
         "dots": 6.231276512145996, "false": 10.259596824645996}),
    "gpt2-45m-moe8-b32-t1000": (
        ModelConfig(compute_dtype="bfloat16", num_experts=8), 32, 1000,
        dict(family="gpt2"), "dots",
        {"true": 6.670892715454102, "attn_proj": 6.670892715454102,
         "ffn": 9.600580215454102, "flash": 9.978235244750977,
         "dots": 11.076868057250977, "false": 16.570032119750977}),
}


@pytest.mark.parametrize("shape", sorted(PINNED))
def test_memory_estimates_and_rung_are_the_parents(shape):
    cfg, batch, seqlen, layout, picked, gib = PINNED[shape]
    for rung, want in gib.items():
        assert memory.estimate_step_gib(cfg, batch, seqlen, rung,
                                        **layout) == want, rung
    assert memory.select_remat(cfg, batch, seqlen, budget_gib=V5E_GIB,
                               verbose=False, **layout) == picked


@pytest.mark.parametrize("shape,tp,local_batch,want", [
    ("gpt2-medium.train-b12-t1024", 1, 12, "ffn"),
    ("gpt2-large.train-dp2-tp2", 2, 8, "dots"),
    ("llama-45m-b32-t1000", 1, 32, "dots"),
])
def test_traced_rung_is_the_parents(shape, tp, local_batch, want):
    """What the model itself picks while it is traced (never 'false')."""
    cfg, _, seqlen, layout, _, _ = PINNED[shape]
    model = build_model(layout["family"], cfg, tp_size=tp,
                        remat_budget_gib=V5E_GIB)
    shapes = _shapes(model)
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree)) // tp
    memory.select_remat_traced.cache_clear()
    assert memory.select_remat_traced(
        model, count(shapes), count(shapes["layers"]), local_batch,
        seqlen) == want

"""The EASTER language-model federation a configuration file describes.

Shared by the runners of the language-model cells. The program's model
registry supplies the architecture by name (``program_arch``); the file's
published sizes are checked against it, so that a cell runs the widths its
configuration states or does not run at all.
"""
from __future__ import annotations

# configuration key -> the program's ModelConfig attribute
WIDTHS = {"hidden_size": "d_model", "intermediate_size": "d_ff",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "resolved_head_dim",
          "vocab_size": "vocab_size", "rope_theta": "rope_theta",
          "rms_norm_eps": "rms_eps", "qkv_bias": "qkv_bias",
          "num_hidden_layers": "n_layers", "torch_dtype": "dtype"}


def build(ctx):
    """(system, parameter shapes) for ``ctx.config``."""
    import dataclasses
    import jax
    from repro.configs.base import EasterConfig, get_config
    from repro.core.easter_lm import EasterLM
    from bench.harness import BenchError

    c, e = ctx.config, ctx.config["easter"]
    mc = dataclasses.replace(get_config(c["program_arch"]),
                             n_layers=c["num_hidden_layers"])
    for key, attr in WIDTHS.items():
        if getattr(mc, attr) != c[key]:
            raise BenchError(f"{c['program_arch']}: {attr} is "
                             f"{getattr(mc, attr)!r}, the configuration "
                             f"states {key} = {c[key]!r}")
    if c["hidden_act"] != mc.act or mc.family != "dense":
        raise BenchError(f"{c['program_arch']} is not a dense SwiGLU model")
    system = EasterLM(cfg=mc, easter=EasterConfig(
        num_passive=e["num_passive"], d_embed=e["d_embed"],
        decision_layers=e["decision_layers"],
        passive_depth_frac=e["passive_depth_frac"], mask_mode=e["wire"]))
    shapes = jax.eval_shape(system.init_params, jax.random.PRNGKey(0))
    return system, shapes


def initial_parties(ctx, shapes, *, served: bool = False):
    """The federation's weights from the seed (the reference's too).
    ``served``: the weights a serving cell serves, whose biases and norm
    scales the configuration's ``served_weights`` draws away from their
    init values."""
    from bench import weights
    draw = ctx.config.get("served_weights", {}) if served else {}
    return weights.fill(shapes, ctx.key(1),
                        bias_std=draw.get("bias_std", 0.0),
                        scale_std=draw.get("norm_scale_std", 0.0))["parties"]

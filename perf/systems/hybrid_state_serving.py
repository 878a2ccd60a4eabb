"""One chip's share of a hybrid decoder — recurrent-state (KDA) layers beside latent-attention (MLA) layers —
served through the program's normal path.

``perf/systems/mla_moe_serving.run`` with this model's four things: ``engine`` (``BailingHybridTransformer``
under ``Generator`` + ``ContinuousBatcher``: the engine's pool holds a latent page pool for the MLA layers and
one row a slot of state for the KDA layers), ``counters`` (that file's, plus the model's ``state_*`` counters), ``references`` (``perf/reference/bailing_hybrid_decoder.py``, by ``--control``) and
``extra_numbers`` (that file's median). No line of the window is copied.

``--fault`` plants, beside that file's ``token_altered``, two faults in the state's own path, which the limits of
a cell over this system must catch: ``no_decay`` (the program's KDA layers forget nothing: ``g = 0``) and
``state_bf16`` (the recurrent state held in bfloat16 between steps). Both build the program wrong and leave the
reference as the configuration states it.
"""

from __future__ import annotations

import argparse
import functools
import types
from typing import Any, Dict, Mapping, Optional

from perf.reference import bailing_hybrid_decoder as reference
from perf.systems import mla_moe_serving as base

# imported here, not where they are used: a program without the model (a commit before it) fails as this file is
# imported, within seconds, before any weight is made
from unionml_tpu.models import BailingHybridConfig, BailingHybridTransformer

#: the faults planted in how the program is built: the module configuration's fields each overrides
STATE_FAULTS = {"no_decay": {"kda_lower_bound": 0.0}, "state_bf16": {"state_dtype": "bfloat16"}}


def module_config(cfg: Mapping[str, Any], **overrides: Any):
    """The configuration file's keys as the program's ``BailingHybridConfig``."""
    import jax.numpy as jnp

    fields = {**dict(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], layer_types=tuple(cfg["layer_types"]), layer_group_size=cfg["layer_group_size"],
        kda_head_dim=cfg["head_dim"], conv_size=cfg["short_conv_kernel_size"], kda_lower_bound=float(cfg["kda_lower_bound"]),
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"], qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], hidden_dim=cfg["intermediate_size"], moe_hidden_dim=cfg["moe_intermediate_size"],
        n_experts=cfg["router_experts"], experts_held=(cfg.get("experts_first", 0), cfg["num_experts"]),
        k=cfg["num_experts_per_tok"], n_group=cfg["n_group"], topk_group=cfg["topk_group"],
        n_shared_experts=cfg["num_shared_experts"], n_dense_layers=cfg["first_k_dense_replace"],
        route_norm=bool(cfg["norm_topk_prob"]), route_scale=float(cfg["routed_scaling_factor"]),
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]), max_seq_len=cfg["max_position_embeddings"],
        state_dtype=cfg["precision"]["state_dtype"], param_dtype=jnp.bfloat16, dtype=cfg["precision"]["compute_dtype"],
    ), **overrides}
    for key in ("state_dtype", "dtype", "param_dtype"):
        fields[key] = jnp.dtype(fields[key])
    return BailingHybridConfig(**fields)


def build_engine(cfg: Mapping[str, Any], cell: Mapping[str, Any], weights: Any, control: Optional[str], **planted: Any):
    """BailingHybridTransformer + Generator + ContinuousBatcher at the configuration's sizes; ``planted`` overrides
    module fields (a fault)."""
    from unionml_tpu.models import GenerationConfig, Generator
    from unionml_tpu.serving import ContinuousBatcher

    engine = {**cfg["engine"], **cell["engine"]}
    chunk = int(engine["admit_chunk"])
    max_prompt = int(engine.pop("max_prompt_tokens"))
    max_new = int(engine.pop("max_new_tokens"))
    buckets = tuple(range(chunk, -(-max_prompt // chunk) * chunk + 1, chunk))
    if control not in (None, "int8"):
        raise ValueError(f"unknown control precision {control!r}")
    # the engine is the sound one under the control too: the lower precision is put into the reference (``REFERENCES``)
    gen_cfg = GenerationConfig(max_new_tokens=max_new, temperature=0.0, prompt_buckets=buckets)
    gen = Generator(BailingHybridTransformer(module_config(cfg, **planted)), weights, gen_cfg)
    return gen, ContinuousBatcher(gen, **engine)


def _counters(batcher: Any) -> Dict[str, Any]:
    """``mla_moe_serving``'s counters plus the recurrent state's: the model's ``state_*`` over all dispatches."""
    flat = base._counters(batcher)
    state = batcher.stats().get("state", {})
    flat.update({k: v for k, v in state.items() if k.startswith("state_") and k != "state_bytes_live"})
    return flat


#: the plain reference by ``--control``: under ``int8`` its matrices are rounded to int8 and the program stays sound
#: (the program's own int8 pages have no form over a latent plane or a recurrent state: both raise)
REFERENCES = {
    None: reference,
    "int8": types.SimpleNamespace(
        make_weights=reference.make_weights, logits_at=functools.partial(reference.logits_at, int8_weights=True)
    ),
}


def run(ctx: Any) -> Dict[str, Any]:
    planted = STATE_FAULTS.get(ctx.args.fault)
    if planted is not None:  # built into the engine below; the base's own fault hook knows nothing of it
        args = argparse.Namespace(**{**vars(ctx.args), "fault": None})
        ctx = types.SimpleNamespace(**{**vars(ctx), "args": args})
    return base.run(
        ctx, engine=functools.partial(build_engine, **(planted or {})), counters=_counters, references=REFERENCES,
        extra_numbers=base.median_square,
    )

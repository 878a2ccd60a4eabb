"""A window-and-global routed-experts decoder (SmallThinker) served through the program's normal path.

``perf/systems/mla_moe_serving.run`` with this model's four things: ``engine`` (``SmallThinkerTransformer`` under
``Generator`` + ``ContinuousBatcher``: keys and values of both kinds of layer in one paged pool), ``counters`` (that
file's, plus the sliding layers' ``window_positions_*`` and the chunks' ``kv_positions_*``), ``references``
(``perf/reference/smallthinker_decoder.py``, by ``--control``) and ``extra_numbers`` (that file's median). No line of
the window is copied.

``--fault`` plants, beside that file's ``token_altered``, three faults in how the program is built, which the
limits of a cell over this system must catch: ``router_after_attention`` (the router reads the experts' own input,
``mlp_norm(h)``, not the block's), ``silu_experts`` (SwiGLU experts, not ReGLU) and ``no_window`` (the sliding
layers read the whole row, their rotary kept). Each builds the program wrong and leaves the reference as the
configuration states it.
"""

from __future__ import annotations

import argparse
import functools
import types
from typing import Any, Dict, Mapping, Optional

from perf.reference import smallthinker_decoder as reference
from perf.systems import mla_moe_serving as base

# imported here, not where they are used: a program without the model (a commit before it) fails as this file is
# imported, within seconds, before any weight is made
from unionml_tpu.models import SmallThinkerConfig, SmallThinkerTransformer

#: the faults planted in how the program is built: the module configuration's fields each overrides (by its layer count)
FAULTS = {
    "router_after_attention": lambda layers: {"route_before_attention": False},
    "silu_experts": lambda layers: {"activation": "silu"},
    "no_window": lambda layers: {"sliding_window_layout": (0,) * layers},
}


def module_config(cfg: Mapping[str, Any], **overrides: Any):
    """The configuration file's keys as the program's ``SmallThinkerConfig``."""
    import jax.numpy as jnp

    if not cfg["moe_primary_router_apply_softmax"]:
        raise ValueError("the program routes SmallThinker's experts by a softmax over the router's logits")
    return SmallThinkerConfig(**{**dict(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        moe_hidden_dim=cfg["moe_ffn_hidden_size"], n_experts=cfg["router_experts"],
        experts_held=(cfg.get("experts_first", 0), cfg["num_experts"]), k=cfg["moe_num_active_primary_experts"],
        route_norm=bool(cfg["norm_topk_prob"]), sliding_window=cfg["sliding_window_size"],
        sliding_window_layout=tuple(cfg["sliding_window_layout"]), rope_layout=tuple(cfg["rope_layout"]),
        rope_theta=float(cfg["rope_theta"]), norm_eps=float(cfg["rms_norm_eps"]), max_seq_len=cfg["max_position_embeddings"],
        param_dtype=jnp.bfloat16, dtype=jnp.dtype(cfg["precision"]["compute_dtype"]),
    ), **overrides})


def build_engine(cfg: Mapping[str, Any], cell: Mapping[str, Any], weights: Any, control: Optional[str], fault: Optional[str] = None):
    """SmallThinkerTransformer + Generator + ContinuousBatcher at the configuration's sizes; ``fault`` names one of
    :data:`FAULTS`, built into the module."""
    from unionml_tpu.models import GenerationConfig, Generator
    from unionml_tpu.serving import ContinuousBatcher

    engine = {**cfg["engine"], **cell["engine"]}
    chunk = int(engine["admit_chunk"])
    max_prompt = int(engine.pop("max_prompt_tokens"))
    max_new = int(engine.pop("max_new_tokens"))
    buckets = tuple(range(chunk, -(-max_prompt // chunk) * chunk + 1, chunk))
    if control not in (None, "int8"):
        raise ValueError(f"unknown control precision {control!r}")
    planted = FAULTS[fault](int(cfg["num_hidden_layers"])) if fault else {}
    # the engine is the sound one under the control too: the lower precision is put into the reference (``REFERENCES``)
    gen_cfg = GenerationConfig(max_new_tokens=max_new, temperature=0.0, prompt_buckets=buckets)
    gen = Generator(SmallThinkerTransformer(module_config(cfg, **planted)), weights, gen_cfg)
    return gen, ContinuousBatcher(gen, **engine)


def _counters(batcher: Any) -> Dict[str, Any]:
    """``mla_moe_serving``'s counters plus the model's own: ``window_positions_live`` / ``_read`` (the sliding
    layers' decode reads), ``kv_positions_attended`` / ``_needed`` (the chunks' reads) and
    ``decode_window_pages_skipped``, over all dispatches."""
    flat = base._counters(batcher)
    stats = batcher.stats()
    flat.update({k: v for k, v in stats.get("window", {}).items() if k != "decode"})
    flat.update({k: stats[k] for k in ("kv_positions_attended", "kv_positions_needed", "decode_window_pages_skipped") if k in stats})
    return flat


#: the plain reference by ``--control``: under ``int8`` its matrices are rounded to int8 and the program stays sound
REFERENCES = {
    None: reference,
    "int8": types.SimpleNamespace(
        make_weights=reference.make_weights, logits_at=functools.partial(reference.logits_at, int8_weights=True)
    ),
}


def run(ctx: Any) -> Dict[str, Any]:
    fault = ctx.args.fault if ctx.args.fault in FAULTS else None
    if fault is not None:  # built into the engine below; the base's own fault hook knows nothing of it
        args = argparse.Namespace(**{**vars(ctx.args), "fault": None})
        ctx = types.SimpleNamespace(**{**vars(ctx), "args": args})
    return base.run(
        ctx, engine=functools.partial(build_engine, fault=fault), counters=_counters, references=REFERENCES,
        extra_numbers=base.median_square,
    )

"""An encoder fine-tuned through the program's normal path: ``Model.train`` in step
mode -> ``train.fit`` -> the jitted ``(state, batch) -> (state, metrics)`` step
built by ``make_train_step(classification_loss)``, rows fed from the host each
step through ``@dataset.reader``/``parser`` and the trainer's prefetch iterator.

``fit`` is the program's unit of work and exposes no state between steps, so one
app object (one ``Model``, one registered trainer, hence one compiled step) is
driven through several ``model.train`` calls that hand the state on: 1 step and 2
more (the three steps the reference follows), a calibration run, then the
window, whose step count is fixed from the calibration so that it lasts about
``--seconds``. Inside a call, ``log_every_steps`` makes ``fit`` fence and log
every k-th step; the harness stamps those log records with the host clock, and
the rate is taken between the window call's first and last stamp: all the steps
and all the time between them, the call's first k steps (re-trace, first
dispatch) left to set-up. A handler that only reads the clock is the one hook
``fit`` offers; it changes nothing the step does.
"""

from __future__ import annotations

import gc
import logging
import re
import threading
import time
from typing import Any, Dict, List, Mapping, Optional, Tuple

import numpy as np

from perf import compare
from perf.reference import encoder as reference

_STEP_LOG = re.compile(r"^step (\d+): ")


class StepClock(logging.Handler):
    """Host-clock stamps of ``fit``'s per-step log lines (each follows a fence)."""

    def __init__(self) -> None:
        super().__init__(level=logging.INFO)
        self.stamps: List[Tuple[int, float]] = []

    def emit(self, record: logging.LogRecord) -> None:
        match = _STEP_LOG.match(record.getMessage())
        if match:
            self.stamps.append((int(match.group(1)), time.perf_counter()))


def build_app(cfg: Mapping[str, Any], cell: Mapping[str, Any], holder: Dict[str, Any], fault: Optional[str]):
    """The app a user would write: BertEncoder, classification_loss, AdamW."""
    import optax
    from flax.training import train_state

    from unionml_tpu import Dataset, Model, TrainerConfig, make_train_step
    from unionml_tpu.models import BertConfig, BertEncoder, classification_loss

    trainer_cfg = cfg["trainer"]
    module = BertEncoder(BertConfig(
        vocab_size=cfg["vocab_size"], dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"], hidden_dim=cfg["intermediate_size"],
        max_seq_len=cfg["max_position_embeddings"], type_vocab_size=cfg["type_vocab_size"], num_classes=cfg["num_labels"],
    ))
    tx = optax.adamw(
        trainer_cfg["learning_rate"], b1=trainer_cfg["b1"], b2=trainer_cfg["b2"], eps=trainer_cfg["eps"],
        weight_decay=trainer_cfg["weight_decay"],
    )
    step = make_train_step(
        lambda p, batch: classification_loss(lambda pp, t: module.apply({"params": pp}, t), p, batch), has_aux=True
    )
    dataset = Dataset(name="sst2_shaped")
    model = Model(name="perf-encoder", dataset=dataset)

    @dataset.reader
    def reader(call: int) -> np.ndarray:
        return holder["rows"][call]

    @dataset.parser
    def parser(data: np.ndarray, features: Optional[List[str]], targets: List[str]) -> Tuple[np.ndarray, np.ndarray]:
        return data[:, :-1], data[:, -1]

    @model.init
    def init(hyperparameters: dict) -> Any:
        if holder.get("state") is None:
            holder["state"] = train_state.TrainState.create(apply_fn=module.apply, params=holder["weights"], tx=tx)
        return holder["state"]

    config = TrainerConfig(
        epochs=1, batch_size=int(cell["trainer"]["batch"]), shuffle=False, log_every_steps=int(holder["log_every"]),
    )

    if fault == "state_unchanged":
        @model.trainer(config=config)
        def trainer(state: Any, batch: Any) -> tuple:
            _, metrics = step(state, batch)
            return state, metrics
    elif fault == "half_batch":
        @model.trainer(config=config)
        def trainer(state: Any, batch: Any) -> tuple:
            half = int(cell["trainer"]["batch"]) // 2
            return step(state, tuple(leaf[:half] for leaf in batch))
    else:
        @model.trainer(config=config)
        def trainer(state: Any, batch: Any) -> tuple:
            return step(state, batch)

    return model


def _train(model: Any, holder: Dict[str, Any], call: int) -> Any:
    """One ``model.train`` call over ``holder['rows'][call]``; the state is handed on."""
    model.train(call=call)
    result = model.last_fit_result
    holder["state"] = result.state
    return result


def run(ctx: Any) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from unionml_tpu._logging import logger

    cfg, cell, mix, args = ctx.config, ctx.cell, ctx.mix, ctx.args
    batch, seq = int(cell["trainer"]["batch"]), int(mix["seq"])
    log_every = int(cell["trainer"]["log_every_steps"])
    check_steps = int(cell["check"]["steps"])
    calibration = int(cell["trainer"]["calibration_steps"])
    seconds = float(args.seconds)
    clock = StepClock()
    logger.addHandler(clock)
    previous_level = logger.level
    logger.setLevel(logging.INFO)

    weights = reference.make_weights(cfg, args.seed)
    # the reference starts from the same numbers in buffers of its own (fit donates the program's)
    start_params = jax.tree_util.tree_map(jnp.copy, weights)
    holder: Dict[str, Any] = {"weights": weights, "state": None, "log_every": 1, "rows": {}}
    check_rows = ctx.traffic.rows(mix, args.seed, cfg["vocab_size"], check_steps * batch, stream=0)
    holder["rows"][0] = check_rows[:batch]
    holder["rows"][1] = check_rows[batch:]
    holder["rows"][2] = ctx.traffic.rows(mix, args.seed, cfg["vocab_size"], calibration * batch, stream=1)
    model = build_app(cfg, cell, holder, args.fault)
    trainer_config = model._trainer_config

    # ---- set-up: the three steps the reference follows, through the window's own call and feed
    trainer_config.log_every_steps = 1
    first = _train(model, holder, 0)
    b1 = float(cfg["trainer"]["b1"])
    # the first gradient as the optimizer got it, from its state after one step (mu = (1 - b1) g)
    program_grad = jax.tree_util.tree_map(lambda m: m / (1.0 - b1), holder["state"].opt_state[0].mu)
    rest = _train(model, holder, 1)
    program_losses = [float(h["loss"]) for h in first.history] + [float(h["loss"]) for h in rest.history]
    program_change = jax.tree_util.tree_map(lambda a, b: a.astype(jnp.float32) - b, holder["state"].params, start_params)
    # ---- calibration: how long a step takes, fenced every log_every steps as the window will be
    trainer_config.log_every_steps = log_every
    clock.stamps.clear()
    _train(model, holder, 2)
    stamps = clock.stamps[:]
    if len(stamps) < 2:
        raise RuntimeError(f"calibration logged {len(stamps)} steps; calibration_steps must be at least 2 x log_every_steps")
    step_s = (stamps[-1][1] - stamps[0][1]) / (stamps[-1][0] - stamps[0][0])
    groups = max(2, int(round(seconds / (step_s * log_every))))
    window_steps = (groups + 1) * log_every  # the call's first group is left to set-up
    holder["rows"][3] = ctx.traffic.rows(mix, args.seed, cfg["vocab_size"], window_steps * batch, stream=2)
    gc.collect()
    gc.freeze()

    # ---- the window: one model.train call; timed between its first and last stamp
    clock.stamps.clear()
    compiles_before = ctx.compile_meter.count
    tracer = None
    if args.trace:
        # a slice of the window, from a thread of its own: fit is one call and the profiler is process-wide
        offset = float(cell.get("trace_offset_s", min(2.0, seconds / 4)))
        length = min(float(cell.get("trace_seconds", 3.0)), max(seconds - offset - 0.5, 0.5))

        def trace_slice() -> None:
            time.sleep(offset)
            ctx.start_trace()
            time.sleep(length)
            ctx.stop_trace()

        tracer = threading.Thread(target=trace_slice, name="perf-trace", daemon=True)
        tracer.start()
    call_started = time.perf_counter()
    window = _train(model, holder, 3)
    call_s = time.perf_counter() - call_started
    if tracer is not None:
        tracer.join()
    stamps = clock.stamps[:]
    out: Dict[str, Any] = {"compiles_in_window": ctx.compile_meter.count - compiles_before}
    out["memory_peak_bytes"] = ctx.memory_peak_bytes()
    logger.removeHandler(clock)
    logger.setLevel(previous_level)
    if len(stamps) < 2:
        raise RuntimeError(f"the window logged {len(stamps)} steps")
    (step_a, t_a), (step_b, t_b) = stamps[0], stamps[-1]
    steps_timed, window_s = step_b - step_a, t_b - t_a
    # set-up ends where the timed steps begin: process start .. first stamp of the window call
    out["setup_s"] = ctx.process_age_s() - (time.perf_counter() - t_a)
    out["e2e"] = {"train_tokens_per_s": steps_timed * batch * seq / window_s, "step_ms": window_s / steps_timed * 1e3}
    out["attempted"] = int(window.steps)
    losses = [float(h["loss"]) for h in window.history]
    out["failed"] = int(sum(1 for x in losses if not np.isfinite(x)))
    out["early"] = {
        "window_steps": int(window.steps), "steps_timed": steps_timed, "window_s": window_s, "call_s": call_s,
        "calibrated_step_ms": step_s * 1e3,
        # the slowest stretch between two fenced steps: a host stall shows here, a slower device everywhere
        "slowest_group_ms": max((b[1] - a[1]) / (b[0] - a[0]) for a, b in zip(stamps, stamps[1:])) * 1e3, "first_step_of_call_s": window.compile_time_s,
        "compiles_in_window": out["compiles_in_window"], "loss_first": losses[0] if losses else None,
        "loss_last": losses[-1] if losses else None, "fit_samples_per_s": window.samples_per_sec,
    }
    out["facts"] = {
        "kind": "training", "window_s": window_s, "steps_timed": steps_timed, "batch": batch, "seq": seq, "config": cfg,
        "chips": int(cell["chips"]), "call_steps": int(window.steps), "call_s": call_s,
    }

    # ---- free the program's state, then follow the first three steps with the plain reference
    del model, window, first, rest
    holder.clear()
    gc.unfreeze()
    gc.collect()
    batches = [(check_rows[i * batch : (i + 1) * batch, :-1], check_rows[i * batch : (i + 1) * batch, -1]) for i in range(check_steps)]
    started = time.monotonic()
    row_block = int(cell["check"]["row_block"])
    ref = reference.train(start_params, cfg, cfg["trainer"], batches, row_block)
    ref_change = jax.tree_util.tree_map(lambda a, b: a - b, ref["params"], start_params)
    if args.control:
        # the control: the reference put in the program's place, in the lower precision
        stand_in = reference.train(start_params, cfg, cfg["trainer"], batches, row_block, quant=args.control)
        program_losses, program_grad = stand_in["losses"], stand_in["first_grad"]
        program_change = jax.tree_util.tree_map(lambda a, b: a - b, stand_in["params"], start_params)
    program_grad_norms, ref_grad_norms = compare.leaf_norms(program_grad), compare.leaf_norms(ref["first_grad"])
    program_change_norms, ref_change_norms = compare.leaf_norms(program_change), compare.leaf_norms(ref_change)
    idle = compare.idle_gradient_leaves(ref_grad_norms)
    limits = cell.get("limits", {})
    # the first gradient's rounding noise, as a share of what the lower-precision control adds on the same rows and
    # weights: seeds differ fourfold in how much noise a gradient carries, the ratio to the control's does not
    control_grad = reference.loss_and_grad(start_params, cfg, *batches[0], row_block, quant=cell["check"]["control"])[1]
    noise = compare.median_leaf_difference(program_grad, ref["first_grad"])
    control_noise = compare.median_leaf_difference(control_grad, ref["first_grad"])
    loss_gaps = [abs(got - want) / max(abs(want), 1e-30) for got, want in zip(program_losses, ref["losses"])]
    grad_gap, grad_leaf = compare.worst_leaf_gap(program_grad_norms, ref_grad_norms)
    change_gap, change_leaf = compare.worst_leaf_gap(program_change_norms, ref_change_norms, skip=idle)
    numbers: List[Any] = [
        ("grad_norm_gap", grad_gap, limits.get("grad_norm_gap")),
        ("param_change_gap", change_gap, limits.get("param_change_gap")),
        ("grad_noise_vs_control", (noise / control_noise) ** 2, limits.get("grad_noise_vs_control")),
    ]
    numbers.append(("nonfinite_losses", float(out["failed"]), 0.0))
    out["early"]["check"] = {
        "seconds": time.monotonic() - started, "program_losses": program_losses, "reference_losses": ref["losses"],
        # printed, not compared: no control or fault reads far enough from the sound runs on them (PERF.md, section 2)
        "loss_rel_by_step": loss_gaps, "grad_diff_median": noise, "control_grad_diff_median": control_noise,
        "grad_worst_leaf": grad_leaf, "change_worst_leaf": change_leaf, "idle_leaves": idle,
        "median_change_gap": float(np.median([
            abs(program_change_norms[n] - ref_change_norms[n]) / max(ref_change_norms[n], 1e-30) for n in ref_change_norms if n not in idle
        ])),
    }
    out["numbers"] = numbers
    return out

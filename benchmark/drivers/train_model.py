"""Train driver for any model the program trains: a configuration trained
through the program's own path, `JaxTrainer` -> worker group ->
`ShardedTrainStep`, on the token batches the cell's traffic generator feeds
it.  The loop is `drivers/train_step.py`'s; the two points that bound that
file to one family are data here:

    "program":   {"module": "ray_tpu.models.<m>", "config": "<dataclass>"}
                 the model group's keys that are fields of the dataclass
                 become its arguments, with the train group's
                 `remat_policy` and `fused_ce`;
    "reference": "<file under benchmark/reference/>", which offers
                 `dims_from_config(model)` and `Pass(params, tokens, dims,
                 for_grads)`: one sequence through the plain float32
                 model, with `token_nll(targets)` [seq], `grads(targets)`
                 (the gradient of the mean of that, a layer at a time) and
                 the method `reference_check.probe` names, which gives
                 (operands, result) of one inner computation; the
                 program's module offers `token_nll(params, batch, config)`
                 and that probe as a function of (*operands, config).

So the next architecture is a configuration file, a reference file and a
model file.  A checkout whose program lacks the module (an earlier commit)
fails within seconds, before the runtime starts.

The mix names its generator (`benchmark/generators/<kind>.py`); this
driver asks it for a `plan(mix, seed, seconds)` here and, on the worker,
for `batches(plan, rows, seq, vocab)`, an iterator of int32 arrays
`[rows, seq + 1]`, one a step.  It does not know one kind from another.

The loop below runs ON the train worker, which holds the chip(s); this
process never initialises a JAX backend.  The loop is the benchmark's (a
user's train_loop_per_worker), the step is the program's.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, List

from benchmark import harness
from benchmark.harness import BenchFailure, say

# AdamW's first-moment decay and the global-norm clip, as handed to
# `default_optimizer` (its own defaults): the first step's gradient is read
# back from the first moment with them
ADAM_B1, GRAD_CLIP = 0.9, 1.0


def build_config(program: dict, model: dict, tr: dict):
    """The program's config dataclass from the configuration file."""
    import dataclasses
    import importlib

    cls = getattr(importlib.import_module(program["module"]),
                  program["config"])
    fields = {f.name for f in dataclasses.fields(cls)}
    return cls(**{k: v for k, v in model.items() if k in fields},
               remat_policy=tr["remat_policy"], fused_ce=tr["fused_ce"])


def first_step_gradient(state, grad_norm: float, ref_grads: dict,
                        rows: int = 1) -> dict:
    """The gradient the step program itself computed in its FIRST step,
    against the reference's.  The step starts from zero moments, so the
    first moment it leaves is (1 - b1) x the clipped gradient, and
    `grad_norm` (before the clip) undoes the clip: no other program runs
    the model.  `ref_grads`: {(keys into the parameters [+ the repeat],
    name): host array}, summed over `rows` sequences; they go back to the
    device in the parameters' layout and one program takes the differences.
    -> the relative error |g - ref| / |ref| of all parameters together
    (`rel`) and of each (`by_leaf`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    mu = next(x for x in jax.tree.leaves(
        state["opt_state"], is_leaf=lambda x: hasattr(x, "mu"))
        if hasattr(x, "mu")).mu
    scale = max(grad_norm, GRAD_CLIP) / GRAD_CLIP / (1.0 - ADAM_B1)
    names, want, stacked = [], [], []
    for path, leaf in jax.tree_util.tree_flatten_with_path(mu)[0]:
        keys = tuple(k.key for k in path)
        stacked.append(keys[0] == "layers")     # a leading axis of repeats
        if stacked[-1]:
            names += ["/".join([*keys[:3], str(rep), keys[3]])
                      for rep in range(leaf.shape[0])]
            want.append(np.stack([ref_grads[keys[:3] + (rep,), keys[3]]
                                  for rep in range(leaf.shape[0])]))
        else:
            names.append("/".join(keys))
            want.append(ref_grads[keys, None])

    @jax.jit
    def squares(mu, want):
        """Per parameter (and repeat): |g - ref|^2 and |ref|^2."""
        err, ref = [], []
        for g, w, many in zip(jax.tree.leaves(mu), want, stacked):
            g = (g.astype(jnp.float32) * scale).reshape(
                g.shape[0] if many else 1, -1)
            w = w.reshape(g.shape) / rows
            err.append(jnp.sum((g - w) ** 2, axis=1))
            ref.append(jnp.sum(w ** 2, axis=1))
        return jnp.concatenate(err), jnp.concatenate(ref)

    err, ref = (np.asarray(a, np.float64) for a in squares(mu, want))
    return {"rel": float((err.sum() / ref.sum()) ** 0.5),
            "reference_norm": float(ref.sum() ** 0.5),
            "program_norm": grad_norm,
            "by_leaf": {k: float((e / r) ** 0.5)
                        for k, e, r in zip(names, err, ref) if r > 0}}


def worst_parameter(by_leaf: dict, excludes=()) -> str:
    """The parameter with the largest relative error, those whose name is
    in `excludes` left out."""
    skip = tuple("/" + n for n in excludes)
    return max((k for k in by_leaf if not k.endswith(skip)),
               key=by_leaf.get)


def train_loop(cfg: Dict[str, Any]) -> None:
    """Runs on the worker.  Reports each step as a user's loop would,
    then one final record with everything the driver reads."""
    import functools
    import glob
    import importlib

    import jax
    import jax.monitoring
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import train
    from ray_tpu.ops import dispatch
    from ray_tpu.parallel.mesh import build_mesh
    from ray_tpu.train.train_state import ShardedTrainStep, default_optimizer

    compiles = {"n": 0}

    def on_event(name, secs, **kw):
        if name.endswith("backend_compile_duration") \
                or "cache_retrieval" in name:
            compiles["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)

    model, tr, plan = cfg["model"], cfg["train"], cfg["plan"]
    chips = cfg["chips"]
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if len(devices) < chips:
        raise RuntimeError(f"the worker sees {len(devices)} devices, the "
                           f"cell needs {chips}")
    seq, batch = tr["sequence_length"], tr["batch_rows"]
    ref = importlib.import_module("benchmark.reference." + cfg["reference"])
    config = build_config(cfg["program"], model, tr)
    mesh = build_mesh(axes=tr["mesh_axes"], devices=devices[:chips])
    moments = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        tr["adam_moment_dtype"]]
    ts = ShardedTrainStep(config, mesh, optimizer=default_optimizer(
        warmup_steps=tr["lr_warmup_steps"], total_steps=tr["lr_total_steps"],
        b1=ADAM_B1, grad_clip=GRAD_CLIP, mu_dtype=moments, nu_dtype=moments))
    seed = cfg["seed"] % (2 ** 31 - 1)
    t = time.perf_counter()
    state = ts.init(jax.random.key(seed))
    jax.block_until_ready(state)
    init_s = time.perf_counter() - t
    feed = harness.load_generator(cfg["generator"]).batches(
        plan, batch, seq, config.vocab_size)
    tokens = next(feed)
    batch_dev = {"tokens": jnp.asarray(tokens)}

    # the largest parameter: where it lies says whether the state is sharded
    big = max(jax.tree.leaves(state["params"]), key=lambda a: a.size)
    shards = big.addressable_shards
    placement = {"shard_devices": sorted({s.device.id for s in shards}),
                 "shard_shape": list(shards[0].data.shape),
                 "full_shape": list(big.shape)}
    del big, shards

    # Reference, before the first step donates the state: the plain
    # float32 model on the same rows with the same weights.  Held against
    # the program's forward (`eval_step`): the mean loss and the NLL of
    # EVERY position (their root-mean-square difference is what a wrong
    # layer moves).  Held against the program's own function of that name:
    # the probe the configuration names, one inner computation on the
    # reference's operands, where no other layer's rounding hides its own.
    # The reference's gradient, a layer at a time, goes to the host: the
    # first timed-program step is held against it below.
    rows = int(tr["reference_rows"])
    if rows != batch:
        raise RuntimeError("the first step is held against the reference "
                           "on its own rows: reference_rows must be "
                           "batch_rows")
    probe = cfg["probe"]
    t = time.perf_counter()
    dims = ref.dims_from_config(model)
    program_nll = np.asarray(ts.eval_step(
        state["params"], {"tokens": jnp.asarray(tokens[:rows])}, "token_nll"))
    ref_nll, ref_grads, probe_sq = [], {}, [0.0, 0.0]
    for r in range(rows):
        run = ref.Pass(state["params"], tokens[r, :-1], dims, for_grads=True)
        ref_nll.append(np.asarray(run.token_nll(tokens[r, 1:])))
        operands, want = getattr(run, probe)()
        got = jax.jit(functools.partial(
            getattr(ts.model, probe), config=config))(*operands)
        probe_sq[0] += float(jnp.sum((got.astype(jnp.float32) - want) ** 2))
        probe_sq[1] += float(jnp.sum(want ** 2))
        del operands, want, got
        for path, grad in run.grads(tokens[r, 1:]):
            for name, g in (grad.items() if isinstance(grad, dict)
                            else [(None, grad)]):
                g = np.asarray(g)
                ref_grads[path, name] = g if r == 0 \
                    else ref_grads[path, name] + g
        del run
    ref_nll = np.stack(ref_nll)
    ref_loss, program_ref_loss = float(ref_nll.mean()), float(
        program_nll.mean())
    token_rms = float(np.sqrt(np.mean((program_nll - ref_nll) ** 2)))
    probe_rel = float(np.sqrt(probe_sq[0] / probe_sq[1]))
    del ref_nll, program_nll
    ref_s = time.perf_counter() - t

    losses: List[float] = []
    grad_check: Dict[str, Any] = {}
    first, batches_moved = True, 1

    def one_step(sync: bool):
        nonlocal state, tokens, batch_dev, first, batches_moved
        if first:       # the first step takes the batch the reference saw
            first = False
        else:
            nxt = next(feed)
            if nxt is not tokens:   # a fixed batch goes to the device once
                tokens, batch_dev = nxt, {"tokens": jnp.asarray(nxt)}
                batches_moved += 1
        with jax.profiler.TraceAnnotation("bench:step_dispatch"):
            state, metrics = ts.step(state, batch_dev)
        if ref_grads:       # the first step only
            grad_check.update(first_step_gradient(
                state, float(metrics["grad_norm"]), ref_grads, rows))
            ref_grads.clear()
        if sync:
            with jax.profiler.TraceAnnotation("bench:sync_loss"):
                losses.append(float(metrics["loss"]))
            with jax.profiler.TraceAnnotation("bench:report"):
                train.report({"step": len(losses), "loss": losses[-1]})

    t = time.perf_counter()
    one_step(True)
    first_step_s = time.perf_counter() - t
    for _ in range(plan["warmup_steps"]):
        one_step(True)

    # the window
    seconds, per_sync = cfg["seconds"], max(1, plan["steps_per_sync"])
    tcfg = cfg.get("trace") or None
    trace_info: Dict[str, Any] = {}
    compiles_before = compiles["n"]
    n_before = len(losses)
    ends: List[float] = []
    steps_done = 0
    t_start_epoch = time.time()
    t_start = time.perf_counter()
    tracing = False
    while True:
        if tcfg and not tracing and not trace_info \
                and time.perf_counter() - t_start >= tcfg["start_s"]:
            os.makedirs(tcfg["dir"], exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            trace_info = {"t_start_epoch": time.time(),
                          "first_step": steps_done}
            jax.profiler.start_trace(tcfg["dir"], profiler_options=opts)
            tracing = True
        steps_done += 1
        one_step(steps_done % per_sync == 0)
        if steps_done % per_sync == 0:
            ends.append(time.perf_counter())
        if tracing and steps_done - trace_info["first_step"] \
                >= tcfg["steps"] and steps_done % per_sync == 0:
            jax.profiler.stop_trace()
            tracing = False
            trace_info["steps"] = steps_done - trace_info["first_step"]
            trace_info["t_end_epoch"] = time.time()
            files = sorted(glob.glob(os.path.join(
                tcfg["dir"], "plugins", "profile", "*", "*.xplane.pb")))
            trace_info["xplane"] = files[-1] if files else None
        if ends and ends[-1] - t_start >= seconds and not tracing:
            break
    window_compiles = compiles["n"] - compiles_before

    train.report({"final": {
        "device": dev, "memory_peak_bytes": harness.peak_bytes(devices),
        "init_s": init_s,
        "first_step_s": first_step_s, "reference_s": ref_s,
        "reference_loss": ref_loss, "program_reference_loss":
        program_ref_loss, "reference_token_rms": token_rms,
        "reference_probe_rel": probe_rel, "gradient": grad_check,
        "placement": placement,
        "losses": losses, "window_first_loss_index": n_before,
        "step_ends": [e - t_start for e in ends],
        "steps": steps_done, "steps_per_sync": per_sync,
        "batches_moved": batches_moved,
        "tokens_per_step": batch * seq,
        "t_start_epoch": t_start_epoch,
        "window_compiles": window_compiles,
        "kernels": dispatch.taken(), "trace": trace_info}})


def run(resolved: dict, args, t_process_start: float) -> dict:
    import importlib.util

    program = resolved["config"]["program"]
    try:
        found = importlib.util.find_spec(program["module"])
    except ModuleNotFoundError:
        found = None
    if found is None:
        raise BenchFailure(f"the program here has no {program['module']}: "
                           f"this checkout cannot run the configuration")
    import ray_tpu
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    cfg, mix, cell = resolved["config"], resolved["mix"], resolved["cell"]
    model, tr = cfg["model"], cfg["train"]
    rehearse, chips = args.rehearse, cell["chips"]
    plan = harness.load_generator(mix["generator"]).plan(
        mix, args.seed, args.seconds)
    trace = None
    if args.trace:
        trace = dict(cfg["trace"], dir=os.path.join(
            harness.OUT_DIR, "trace", cell["name"]))
    ray_tpu.init(num_tpus=chips if rehearse else None)
    try:
        have = ray_tpu.cluster_resources().get("TPU", 0)
        if have < chips:
            raise BenchFailure(f"this host offers {have:g} TPU chips, the "
                               f"cell needs {chips}")
        t = time.perf_counter()
        result = JaxTrainer(
            train_loop,
            train_loop_config={"model": model, "train": tr, "plan": plan,
                               "program": program,
                               "reference": cfg["reference"],
                               "probe": cfg["reference_check"]["probe"],
                               "generator": mix["generator"],
                               "chips": chips, "seed": args.seed,
                               "seconds": args.seconds, "trace": trace},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         tpu_chips_per_worker=chips),
            run_config=RunConfig(
                name=cell["name"],
                storage_path=os.path.join(harness.OUT_DIR, "train")),
        ).fit()
        fit_s = time.perf_counter() - t
    finally:
        ray_tpu.shutdown()
    final = [h["final"] for h in result.metrics_history if "final" in h]
    if not final:
        raise BenchFailure("the train loop sent no final record")
    rec = final[-1]
    harness.check_device(rec["device"], chips, rehearse)
    ends = rec["step_ends"]
    elapsed = ends[-1]
    tokens_per_s = (len(ends) * rec["steps_per_sync"]
                    * rec["tokens_per_step"] / elapsed)
    setup_s = rec["t_start_epoch"] - t_process_start
    window_losses = rec["losses"][rec["window_first_loss_index"]:]
    say("train", fit_s=fit_s, init_s=rec["init_s"],
        first_step_s=rec["first_step_s"], reference_s=rec["reference_s"],
        steps=rec["steps"], batches_moved=rec["batches_moved"],
        elapsed_s=elapsed,
        step_s_median=harness.percentile(
            [b - a for a, b in zip([0.0] + ends, ends)], 50)
        / rec["steps_per_sync"],
        first_loss=rec["losses"][0], last_loss=rec["losses"][-1],
        reference_loss=rec["reference_loss"],
        program_reference_loss=rec["program_reference_loss"],
        reference_token_rms=rec["reference_token_rms"],
        reference_probe_rel=rec["reference_probe_rel"],
        gradient=rec["gradient"], window_compiles=rec["window_compiles"],
        placement=rec["placement"],
        kernels=rec["kernels"], tokens_per_s=tokens_per_s)

    check = cfg["reference_check"]
    faults = []
    if not rehearse:
        k = harness.kernels_ok(rec["kernels"], cfg["must_take_pallas"])
        if k:
            faults.append(k)
    diff = abs(rec["program_reference_loss"] - rec["reference_loss"])
    if not diff <= check["tolerance"]:
        faults.append(f"loss {rec['program_reference_loss']} against the "
                      f"reference's {rec['reference_loss']}: off by {diff}, "
                      f"tolerance {check['tolerance']}")
    # the timed program's own first step, on the same rows and weights
    diff = abs(rec["losses"][0] - rec["reference_loss"])
    if not diff <= check["tolerance"]:
        faults.append(f"the first step's loss {rec['losses'][0]} against "
                      f"the reference's {rec['reference_loss']}: off by "
                      f"{diff}, tolerance {check['tolerance']}")
    if not rec["reference_token_rms"] <= check["token_rms_tolerance"]:
        faults.append(f"per-token NLL off the reference's by "
                      f"{rec['reference_token_rms']} (root mean square), "
                      f"tolerance {check['token_rms_tolerance']}")
    if not rec["reference_probe_rel"] <= check["probe_rel_tolerance"]:
        faults.append(f"{check['probe']} on the reference's operands off "
                      f"the reference's by {rec['reference_probe_rel']} of "
                      f"its root mean square, tolerance "
                      f"{check['probe_rel_tolerance']}")
    grad = rec["gradient"]
    worst = worst_parameter(grad["by_leaf"],
                            check.get("grad_worst_excludes", ()))
    for what, value, limit in (
            ("all parameters", grad["rel"], check["grad_rel_tolerance"]),
            (worst, grad["by_leaf"][worst],
             check["grad_worst_rel_tolerance"])):
        if not value <= limit:
            faults.append(f"the first step's gradient off the reference's "
                          f"by {value} of its norm ({what}), tolerance "
                          f"{limit}")
    if not all(l == l and abs(l) < 1e4 for l in rec["losses"]):
        faults.append("a loss is not finite")
    if mix.get("loss_must_fall") and not window_losses[-1] < window_losses[0]:
        faults.append(f"the loss did not fall over the window: "
                      f"{window_losses[0]} -> {window_losses[-1]}")
    if chips > 1:
        place = rec["placement"]
        if len(place["shard_devices"]) != chips \
                or place["shard_shape"] == place["full_shape"]:
            faults.append(f"parameters are not sharded over {chips} "
                          f"distinct devices: {place}")
    if rec["window_compiles"]:
        faults.append(f"{rec['window_compiles']} compiles inside the window")
    say("correct", faults=faults)
    # steps' median wall time: what the rate is when the profiler's start
    # and stop (seconds, in a traced run) are not in it
    walls = [b - a for a, b in zip([0.0] + ends, ends)]
    steady = (rec["steps_per_sync"] * rec["tokens_per_step"]
              / harness.percentile(walls, 50))
    counters = {
        "steady_tokens_per_s": steady,
        "window_compiles": rec["window_compiles"],
        "batches_moved": rec["batches_moved"],
        "step_ends": ends, "steps_per_sync": rec["steps_per_sync"],
        "tokens_per_step": rec["tokens_per_step"],
        "trace_steps": rec["trace"].get("steps"),
        "values": {"train_tokens_per_s": tokens_per_s},
        "device": rec["device"], "model": model, "train": tr,
        "chips": chips, "seconds": args.seconds,
    }
    return {"correct": not faults and not rehearse,
            "attempted": rec["steps"], "failed": 0,
            "values": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
            "device": rec["device"],
            "memory_peak_bytes": rec["memory_peak_bytes"],
            "spans": [], "counters": counters,
            "trace_file": rec["trace"].get("xplane"),
            "trace_t0_epoch": rec["trace"].get("t_start_epoch")}

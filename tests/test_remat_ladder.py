"""What `ShardedTrainStep` keeps across a layer's remat under
`remat_policy="full"` (PR 40): the first rung where the compiled program
fits the device beside what else it holds, the second where it does not or
the compiler refuses it, one decision for all the processes of a mesh, and
the record in `dispatch.taken()`, on the first `train.step` span and in
`device_stats.program_report`.  On the CPU, a tiny model; the flash kernels
interpreted where the kept names matter (`ops/attention._flash_fwd`)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.ops import dispatch
from ray_tpu.parallel import mesh as mesh_lib
from ray_tpu.train import train_state
from ray_tpu.train.train_state import ShardedTrainStep
from ray_tpu.util import device_stats, tracing

KEPT = "kept:attn_out,attn_lse"


@pytest.fixture(autouse=True)
def _alone(monkeypatch, time_limit):
    time_limit(180)
    monkeypatch.setattr(dispatch, "_taken", {})
    tracing.clear_spans()
    yield
    tracing.clear_spans()


@pytest.fixture
def interpreted(monkeypatch):
    """The flash kernels run (interpreted), so the kept names are in the
    program; without it attention is XLA's and both rungs compile to the
    same program, which is all the ladder's own logic needs."""
    monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")


def _config(**kw):
    return tfm.TransformerConfig.tiny(
        use_flash=True, dtype=jnp.float32, max_seq_len=128, head_dim=32,
        num_layers=2, **kw)


def _step(config, **kw):
    mesh = mesh_lib.build_mesh(axes={"data": 1}, devices=jax.devices()[:1])
    ts = ShardedTrainStep(config, mesh, **kw)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 129), 0,
                                          config.vocab_size)}
    return ts, ts.init(jax.random.key(0)), batch


def _limit(monkeypatch, limit, in_use=0):
    monkeypatch.setattr(
        device_stats, "memory_stats",
        lambda device=None: {"bytes_limit": limit, "bytes_in_use": in_use})


def _aot_compiles(monkeypatch):
    """Counts the compiles the ladder itself asks for (the jitted call
    does not go through `Lowered.compile`)."""
    calls, compile_ = [], jax.stages.Lowered.compile

    def counted(self, *a, **kw):
        calls.append(1)
        return compile_(self, *a, **kw)

    monkeypatch.setattr(jax.stages.Lowered, "compile", counted)
    return calls


def _record():
    (record, times), = dispatch.taken()["train.remat"].items()
    assert times == 1
    return record


def _used(record) -> int:
    return int(record.split("program")[1].split("of")[0])


def _argument_bytes() -> int:
    return device_stats.program_report("train.step")["memory"][
        "argument_bytes"]


def _first_span():
    first = tracing.get_spans(("train.step",))[0]
    tracing.clear_spans()
    return first


def _second_forwards():
    """Instructions of remat's second flash forward in the program that
    `program_report` says ran."""
    rows = device_stats.program_report("train.step")["instructions"]
    return [r[1] for r in rows.values()
            if "checkpoint" in r[1] and "flash_fwd" in r[1]]


@pytest.mark.parametrize("limit", [None, 1 << 40])
def test_the_first_rung_runs_where_the_program_fits(monkeypatch, interpreted,
                                                    limit):
    """The CPU reports no limit (nothing there can refuse the program);
    a device whose limit the program reads under keeps as much."""
    if limit is not None:
        _limit(monkeypatch, limit)
    events = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: events.append(name))
    ts, state, batch = _step(_config())
    compiles = _aot_compiles(monkeypatch)
    events.clear()
    state, metrics = ts.step(state, batch)
    # one lowering and one compile: the call finds what the ladder compiled
    assert sum(e.endswith("backend_compile_duration") for e in events) == 1
    assert sum(e.endswith("jaxpr_to_mlir_module_duration")
               for e in events) == 1
    assert len(compiles) == 1 and ts._keep is True
    record = _record()
    used = _used(record)
    assert record == f"{KEPT},program{used}of{limit},beside0" and used > 0
    attrs = _first_span()["attributes"]
    assert (attrs["remat"], attrs["remat_program_bytes"],
            attrs["remat_beside_bytes"], attrs["bytes_limit"]) == (
                KEPT, used, 0, limit)
    # (d): the report is of the rung that ran, and its bytes are the record's
    assert not _second_forwards()
    report = device_stats.program_report("train.step")
    assert report["memory"]["total_bytes"] == used
    # decided once (the reports above compiled for themselves)
    del compiles[:]
    ts.step(state, batch)
    assert not compiles and len(dispatch.taken()["train.remat"]) == 1


def test_a_program_over_the_limit_takes_the_second_rung(monkeypatch,
                                                        interpreted):
    _limit(monkeypatch, 1000)
    ts, state, batch = _step(_config())
    compiles = _aot_compiles(monkeypatch)
    state, _ = ts.step(state, batch)
    assert ts._keep is False and len(compiles) == 1
    record = _record()
    used = _used(record)
    assert record == f"kept:none,program{used}of1000,beside0" and used > 1000
    attrs = _first_span()["attributes"]
    assert (attrs["remat"], attrs["remat_program_bytes"],
            attrs["bytes_limit"]) == ("kept:none", used, 1000)
    assert _second_forwards()           # today's program: two forwards
    # decided once in a process: a later step compiles nothing
    del compiles[:]
    ts.step(state, batch)
    assert not compiles and len(dispatch.taken()["train.remat"]) == 1


@pytest.mark.parametrize("over", [0, 1])
def test_what_else_the_device_holds_counts_against_the_limit(monkeypatch,
                                                            over):
    """The program is held against the limit less what is in use beside
    its own arguments (the state and the batch are in use already, and are
    not counted twice): to the byte."""
    ts, state, batch = _step(_config())
    ts.step(state, batch)
    used, arguments = _used(_record()), _argument_bytes()
    device_stats.reset()        # the watermark that first program left
    monkeypatch.setattr(dispatch, "_taken", {})
    tracing.clear_spans()
    beside = 12345
    _limit(monkeypatch, used + beside - over, in_use=arguments + beside)
    ts, state, batch = _step(_config())
    ts.step(state, batch)
    assert ts._keep is (not over)
    assert _record() == (f"kept:{'none' if over else 'attn_out,attn_lse'},"
                         f"program{used}of{used + beside - over},"
                         f"beside{beside}")
    assert _first_span()["attributes"]["remat_beside_bytes"] == beside
    # the rung that runs is what the HBM watermark counts, temporaries and
    # all; a refused first rung is not the program that runs
    assert device_stats.ledger()["watermark_bytes"] == (
        arguments + beside if over else used + beside)


def test_the_reading_is_of_the_meshs_devices_the_least_limit(monkeypatch):
    """Limits differ between chips (by 1,536 bytes on the four-chip host):
    the program is held against the least of the mesh's, and against the
    fullest device."""
    devices = jax.devices()[:2]
    asked = []

    def stats(device=None):
        asked.append(device)
        return {"bytes_limit": 1 << 40 if device is devices[0] else 1000,
                "bytes_in_use": 0}

    monkeypatch.setattr(device_stats, "memory_stats", stats)
    config = _config()
    mesh = mesh_lib.build_mesh(axes={"fsdp": 2}, devices=devices)
    ts = ShardedTrainStep(config, mesh)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 129), 0,
                                          config.vocab_size)}
    ts.step(ts.init(jax.random.key(0)), batch)
    assert asked == list(devices) and ts._keep is False
    assert _record().startswith("kept:none,program") \
        and _record().endswith("of1000,beside0")


def test_a_program_the_compiler_refuses_takes_the_second_rung(monkeypatch):
    def refuse(self, *a, **kw):
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: Ran out of memory in memory space hbm")

    monkeypatch.setattr(jax.stages.Lowered, "compile", refuse)
    ts, state, batch = _step(_config())
    state, metrics = ts.step(state, batch)
    assert ts._keep is False and jnp.isfinite(metrics["loss"])
    assert _record() == "kept:none,programNoneofNone,besideNone"
    assert _first_span()["attributes"]["remat"] == "kept:none"


def test_any_other_compile_error_is_the_callers(monkeypatch):
    def fail(self, *a, **kw):
        raise jax.errors.JaxRuntimeError("INTERNAL: something else")

    monkeypatch.setattr(jax.stages.Lowered, "compile", fail)
    ts, state, batch = _step(_config())
    with pytest.raises(jax.errors.JaxRuntimeError, match="INTERNAL"):
        ts.step(state, batch)


@pytest.mark.parametrize("how", ["save_attn", "dots", "no_remat",
                                 "callers_loss"])
def test_the_ladder_is_for_full_remat_of_the_models_own_loss(monkeypatch,
                                                            how):
    """The other policies do what they did, a model without remat has
    nothing to keep, and a caller's loss is not rebuilt: no decision, no
    record, no compile but the call's."""
    config = _config(**{"save_attn": {"remat_policy": "save_attn"},
                        "dots": {"remat_policy": "dots"},
                        "no_remat": {"remat": False}}.get(how, {}))
    kw = {}
    if how == "callers_loss":
        kw["loss_fn"] = lambda p, b: tfm.loss_fn(p, b, config)
    ts, state, batch = _step(config, **kw)
    compiles = _aot_compiles(monkeypatch)
    ts.step(state, batch)
    assert ts._keep is False and not compiles
    assert "train.remat" not in dispatch.taken()
    assert "remat" not in _first_span()["attributes"]


def test_the_kept_config_differs_in_the_policy_alone():
    config = _config()
    ts, _, _ = _step(config)
    assert ts._kept_config == dataclasses.replace(
        config, remat_policy="save_attn")
    assert train_state.SAVE_ATTN_NAMES == ("attn_out", "attn_lse")


def _tiny(module):
    from ray_tpu.models import hybrid, latent_moe, swa_moe

    return {"transformer": lambda **kw: _config(**kw),
            "hybrid": lambda **kw: hybrid.HybridConfig.tiny(
                dtype=jnp.float32, **kw),
            "latent_moe": lambda **kw: latent_moe.LatentMoEConfig.tiny(
                dtype=jnp.float32, **kw),
            "swa_moe": lambda **kw: swa_moe.SwaMoEConfig.tiny(
                dtype=jnp.float32, **kw)}[module]


@pytest.mark.parametrize("module", ["transformer", "hybrid", "latent_moe",
                                    "swa_moe"])
def test_both_rungs_give_equal_loss_and_gradients_in_every_model(
        interpreted, module):
    """(c): every attention entry of `ops/attention.py` the four models
    reach (roped and GQA, windowed, full and cross at value width 128,
    latent parts, windowed with rope at two head counts and a gate): loss
    and gradients of the kept and the bare layer are EQUAL, not close."""
    import importlib

    model = importlib.import_module(f"ray_tpu.models.{module}")
    full = _tiny(module)(remat=True, remat_policy="full")
    kept = dataclasses.replace(full, remat_policy="save_attn")
    params = model.init_params(full, jax.random.key(2))
    batch = {"tokens": jax.random.randint(jax.random.key(3), (2, 129), 0,
                                          full.vocab_size)}
    before = dict(dispatch.taken().get("flash_attention", {}))
    l_full, g_full = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, batch, full)))(params)
    l_kept, g_kept = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, batch, kept)))(params)
    taken = dispatch.taken()["flash_attention"]
    assert taken.get("interpret", 0) > before.get("interpret", 0)
    assert float(l_full) == float(l_kept) and jnp.isfinite(l_full)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g_full),
                            jax.tree.leaves(g_kept)):
        assert jnp.array_equal(a, b), path

"""Comms-layer tests (parallel/comms.py): ZeRO-style sharded weight
updates and compressed gradient sync, pinned against the unsharded fp32
baseline across every runner variant, plus the satellites that rode the
same PR — the shard_map per-device desync reduce and the run_report
--compute drain fold.

Numerical contract (the tiers the README documents):

- ``--shard-optim`` alone is the SAME arithmetic at a different layout:
  final params match the baseline to float reassociation (~1 ulp —
  asserted at 1e-5).
- ``--grad-comms fp16`` with error feedback tracks the fp32 trajectory to
  half-precision rounding (asserted at 1e-3).
- ``--grad-comms int8`` with error feedback keeps the LOSS trajectory
  within 1e-2 of fp32 — the error-feedback residual re-injects what the
  8-bit wire drops, so quantization noise dithers instead of biasing.
"""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, str(Path(__file__).parent.parent / "tools"))
import run_report  # noqa: E402

from distributed_training_comparison_tpu.config import load_config
from distributed_training_comparison_tpu.data import synthetic_dataset
from distributed_training_comparison_tpu.health import (
    check_partial_desync,
    make_partial_fingerprint_fn,
    partial_fingerprints,
)
from distributed_training_comparison_tpu.obs import CompileMonitor, MetricRegistry
from distributed_training_comparison_tpu.parallel import (
    Comms,
    make_compressed_allreduce,
    make_mesh,
    opt_state_bytes,
    quantize_tree,
    replicated_sharding,
    state_shardings,
    zero_opt_shardings,
    zero_partition_spec,
)
from distributed_training_comparison_tpu.parallel.sharding import place_tree
from distributed_training_comparison_tpu.train import (
    Trainer,
    configure_optimizers,
    create_train_state,
    make_chunk_runner,
    make_device_chunk_runner,
)

from conftest import whole_epoch_runner
from test_train import HP, TinyNet


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(backend="ddp")  # (8, 1)


@pytest.fixture(scope="module")
def dp_tp_mesh():
    return make_mesh(model_parallel=2, backend="ddp")  # (4, 2)


@pytest.fixture(scope="module")
def tiny_data():
    x, y = synthetic_dataset(256, num_classes=10, seed=0)
    return jnp.asarray(x), jnp.asarray(y)


def _fresh_state(mesh):
    tx, _ = configure_optimizers(HP, steps_per_epoch=4)
    state = create_train_state(TinyNet(), jax.random.key(0), tx)
    return jax.device_put(state, replicated_sharding(mesh))


def _has_data(spec) -> bool:
    """True when a PartitionSpec assigns any dimension to the data axis."""
    for entry in tuple(spec):
        names = entry if isinstance(entry, (tuple, list)) else (entry,)
        if "data" in names:
            return True
    return False


def _prepared(mesh, comms):
    """State + sharding tree laid out the way the Trainer wires a comms
    run: residual attached under compression, opt state ZeRO-sharded
    under shard_optim."""
    state = _fresh_state(mesh)
    sh = state_shardings(mesh, state)
    if comms is not None and comms.compressing:
        state = state.replace(comms_residual=comms.residual_init(state.params))
        sh = sh.replace(comms_residual=sh.params)
    if comms is not None and comms.shard_optim:
        sh = sh.replace(
            opt_state=zero_opt_shardings(mesh, state.opt_state, sh.opt_state)
        )
    return place_tree(state, sh), sh


# ------------------------------------------------------------ layout rules


def test_zero_partition_spec_rules():
    # largest free divisible dim takes the data axis
    assert zero_partition_spec((64, 32), None, 8) == P("data", None)
    assert zero_partition_spec((3, 3, 3, 8), None, 8) == P(
        None, None, None, "data"
    )
    # occupied dims are skipped; the layout composes with TP
    assert zero_partition_spec((64, 32), P(None, "model"), 8) == P(
        "data", "model"
    )
    # no divisible free dim → base layout unchanged
    assert zero_partition_spec((10,), None, 8) == P(None)
    assert zero_partition_spec((), None, 8) == P()
    # degenerate data axis → unchanged
    assert zero_partition_spec((64, 32), None, 1) == P(None, None)
    # already data-sharded → never double-assigned
    assert zero_partition_spec((64, 32), P("data"), 8) == P("data", None)


def test_zero_opt_shardings_shard_momentum_keep_scalars(mesh):
    state = _fresh_state(mesh)
    base = state_shardings(mesh, state)
    zsh = zero_opt_shardings(mesh, state.opt_state, base.opt_state)
    specs = [
        (np.shape(leaf), sh.spec)
        for leaf, sh in zip(
            jax.tree_util.tree_leaves(state.opt_state),
            jax.tree_util.tree_leaves(zsh),
        )
    ]
    assert any(
        _has_data(s) for shape, s in specs if shape != ()
    ), "no momentum leaf took the data axis"
    assert all(not _has_data(s) for shape, s in specs if shape == ())
    total, per_device = opt_state_bytes(state.opt_state, zsh)
    assert per_device < total  # the footprint claim, host-side


# --------------------------------------------------------- wire primitives


def test_quantize_tree_error_feedback_identity():
    key = jax.random.key(1)
    tree = {
        "w": jax.random.normal(key, (32, 16)) * 3.0,
        "b": jnp.zeros((7,)),
        "n": jnp.arange(4, dtype=jnp.int32),  # non-float passthrough
    }
    same, deq = quantize_tree(tree, "fp32")
    assert same is tree and deq(same) is same

    amax = float(jnp.max(jnp.abs(tree["w"])))
    for mode, dtype, bound in (
        ("fp16", jnp.float16, amax * 2**-10),  # half-precision ulp tier
        ("int8", jnp.int8, amax / 127),  # one quantization level
    ):
        wire, deq = quantize_tree(tree, mode)
        assert wire["w"].dtype == dtype
        assert wire["n"].dtype == jnp.int32  # untouched
        back = deq(wire)
        assert back["w"].dtype == jnp.float32
        err = jnp.max(jnp.abs(back["w"] - tree["w"]))
        assert float(err) <= bound
        # the EF identity: residual is exactly what the wire dropped
        residual = jax.tree_util.tree_map(jnp.subtract, tree["w"], back["w"])
        np.testing.assert_array_equal(
            np.asarray(residual), np.asarray(tree["w"]) - np.asarray(back["w"])
        )

    with pytest.raises(ValueError, match="grad-comms mode"):
        quantize_tree(tree, "fp8")


def test_fp16_wire_saturates_instead_of_overflowing():
    """A FINITE fp32 gradient past fp16's max (65504) must clip on the
    wire, never overflow to inf: the numerics guard checks the RAW
    pre-compression grads, so an inf born on the wire would dequantize
    into the update and poison params PAST the guard.  With error
    feedback the clipped excess lands in the residual (finite) and
    re-injects next step."""
    g = {"w": jnp.asarray([1e5, -3e5, 1.0], jnp.float32)}  # finite, >65504
    wire, deq = quantize_tree(g, "fp16")
    assert bool(jnp.isfinite(wire["w"]).all())
    back = deq(wire)
    np.testing.assert_allclose(
        np.asarray(back["w"]), [65504.0, -65504.0, 1.0], rtol=1e-3
    )
    residual = jax.tree_util.tree_map(jnp.subtract, g, back)
    assert bool(jnp.isfinite(residual["w"]).all())


def test_compressed_allreduce_wire_modes(mesh):
    n = mesh.shape["data"]
    x = jax.random.normal(jax.random.key(0), (n, 16, 8)) * 2.0
    exact = np.asarray(x).mean(0)
    for mode, tol in (("fp32", 1e-6), ("fp16", 5e-3), ("int8", 5e-2)):
        out = make_compressed_allreduce(mesh, mode)({"g": x})["g"]
        np.testing.assert_allclose(np.asarray(out), exact, atol=tol)
    # sum semantics
    out = make_compressed_allreduce(mesh, "fp32", mean=False)({"g": x})["g"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(x).sum(0), atol=1e-5)
    with pytest.raises(ValueError, match="grad-comms mode"):
        make_compressed_allreduce(mesh, "fp8")


# ------------------------------------------- runner-level numerical pinning


def _run_epochs(mesh, data, comms, epochs=2, runner_kind="epoch"):
    x, y = data
    bs = 32
    steps = len(x) // bs
    key = jax.random.key(7)
    state, sh = _prepared(mesh, comms)
    losses = []
    if runner_kind == "epoch":
        runner = whole_epoch_runner(
            mesh, bs, len(x), state_sharding=sh, comms=comms, donate=False
        )
        for e in range(epochs):
            state, stacked = runner(state, x, y, key, jnp.asarray(e))
            losses.append(np.asarray(stacked["loss"]))
    elif runner_kind == "device_chunk":
        runner = make_device_chunk_runner(
            mesh, bs, 3, state_sharding=sh, comms=comms, donate=False
        )
        rem = make_device_chunk_runner(
            mesh, bs, steps % 3, state_sharding=sh, comms=comms, donate=False
        )
        for e in range(epochs):
            start = 0
            while start < steps:
                take = min(3, steps - start)
                r = runner if take == 3 else rem
                state, stacked = r(
                    state, x, y, key, jnp.asarray(e), jnp.asarray(start)
                )
                losses.append(np.asarray(stacked["loss"]))
                start += take
    elif runner_kind in ("chunk", "chunk_donated"):
        donate = runner_kind == "chunk_donated"
        runner = make_chunk_runner(
            mesh, state_sharding=sh, comms=comms, donate=donate
        )
        for e in range(epochs):
            epoch_key = jax.random.fold_in(key, e)
            cx = jnp.stack([x[i * bs:(i + 1) * bs] for i in range(steps)])
            cy = jnp.stack([y[i * bs:(i + 1) * bs] for i in range(steps)])
            state, stacked = runner(state, cx, cy, epoch_key, jnp.asarray(0))
            losses.append(np.asarray(stacked["loss"]))
    return np.concatenate(losses), jax.device_get(state.params), state


@pytest.mark.parametrize(
    "runner_kind", ["epoch", "device_chunk", "chunk", "chunk_donated"]
)
def test_sharded_update_matches_unsharded(mesh, tiny_data, runner_kind):
    """--shard-optim is the same arithmetic at a different layout: every
    runner variant (whole-epoch chunk, device-chunked, host-chunked,
    donated) must land on the baseline's params to float reassociation."""
    base_l, base_p, _ = _run_epochs(mesh, tiny_data, None, runner_kind=runner_kind)
    comms = Comms(mesh, shard_optim=True)
    l, p, state = _run_epochs(mesh, tiny_data, comms, runner_kind=runner_kind)
    np.testing.assert_allclose(l, base_l, atol=1e-5, rtol=1e-5)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5),
        p, base_p,
    )
    # the layout is real: the momentum trace is carried data-sharded
    specs = [
        getattr(leaf.sharding, "spec", P())
        for leaf in jax.tree_util.tree_leaves(state.opt_state)
        if hasattr(leaf, "sharding") and np.ndim(leaf) > 0
    ]
    assert any(
        _has_data(s) for s in specs
    ), f"no opt-state leaf carried data-sharded: {specs}"


def test_fp16_error_feedback_tracks_fp32(mesh, tiny_data):
    base_l, base_p, _ = _run_epochs(mesh, tiny_data, None, epochs=3)
    comms = Comms(mesh, grad_comms="fp16")
    l, p, state = _run_epochs(mesh, tiny_data, comms, epochs=3)
    np.testing.assert_allclose(l, base_l, atol=1e-3, rtol=1e-3)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-3), p, base_p
    )
    # the residual is genuinely carried (a zero residual would mean the
    # error-feedback path traced away)
    res_norm = sum(
        float(jnp.sum(jnp.abs(leaf)))
        for leaf in jax.tree_util.tree_leaves(state.comms_residual)
    )
    assert res_norm > 0.0


def test_int8_error_feedback_loss_trajectory(mesh, tiny_data):
    """int8 + error feedback keeps the loss trajectory within the
    documented 1e-2 of fp32; the sharded+compressed composition (the full
    --shard-optim --grad-comms int8 path) stays within the same tier."""
    base_l, _, _ = _run_epochs(mesh, tiny_data, None, epochs=3)
    l8, _, _ = _run_epochs(mesh, tiny_data, Comms(mesh, grad_comms="int8"), epochs=3)
    assert float(np.abs(l8 - base_l).max()) < 1e-2
    both, _, _ = _run_epochs(
        mesh, tiny_data,
        Comms(mesh, shard_optim=True, grad_comms="int8"), epochs=3,
    )
    assert float(np.abs(both - base_l).max()) < 1e-2


def test_comms_on_dp_tp_mesh(dp_tp_mesh, tiny_data):
    """The ZeRO layout composes with a nontrivial model axis: same
    numerics on a (4, 2) DP×TP mesh (TinyNet's params are replicated over
    'model', so the zero rule exercises the free-dimension path with the
    model axis present)."""
    base_l, base_p, _ = _run_epochs(dp_tp_mesh, tiny_data, None)
    comms = Comms(dp_tp_mesh, shard_optim=True, grad_comms="fp16")
    l, p, _ = _run_epochs(dp_tp_mesh, tiny_data, comms)
    np.testing.assert_allclose(l, base_l, atol=1e-3, rtol=1e-3)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-3), p, base_p
    )


def test_nonfinite_step_keeps_state_and_residual(mesh, tiny_data):
    """The numerics guard composes with the comms update: a NaN-scaled
    fault window skips the ENTIRE update — params, optimizer state, AND
    the error-feedback residual keep their old values."""
    x, y = tiny_data
    comms = Comms(mesh, shard_optim=True, grad_comms="int8")
    state, sh = _prepared(mesh, comms)
    runner = whole_epoch_runner(
        mesh, 32, len(x), state_sharding=sh, comms=comms,
        fault_injection=True, donate=False,
    )
    before = jax.device_get(state.params)
    new_state, stacked = runner(
        state, x, y, jax.random.key(7), jnp.asarray(0),
        (float("nan"), 0, 8),  # every step of the epoch is non-finite
    )
    assert np.asarray(stacked["skipped"]).sum() == 8
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b),
        jax.device_get(new_state.params), before,
    )
    for leaf in jax.tree_util.tree_leaves(new_state.comms_residual):
        np.testing.assert_array_equal(np.asarray(leaf), 0.0)
    assert int(np.asarray(new_state.step)) == 0


def test_benign_path_fingerprint_unchanged(mesh, tiny_data):
    """Both flags off must trace the exact pre-comms update: an INACTIVE
    Comms and comms=None compile to the SAME executable fingerprint (the
    monitor dedups identical fingerprints — one record, two compiles),
    and the default TrainState flattens with no extra leaf."""
    x, y = tiny_data
    state = _fresh_state(mesh)
    n_leaves = len(jax.tree_util.tree_leaves(state))
    assert state.comms_residual is None
    assert len(jax.tree_util.tree_leaves(state.replace())) == n_leaves

    inactive = Comms(mesh)
    assert not inactive.active
    monitor = CompileMonitor(registry=MetricRegistry())
    for comms in (None, inactive):
        runner = whole_epoch_runner(
            mesh, 32, len(x), comms=comms, donate=False, monitor=monitor
        )
        runner(_fresh_state(mesh), x, y, jax.random.key(7), jnp.asarray(0))
    ledger = monitor.ledger()
    assert len(ledger) == 1, [r["fingerprint"] for r in ledger]
    assert ledger[0]["compiles"] == 2


# --------------------------------------------------------------- e2e runs


def _hparams(tmp_path, extra=()):
    return load_config(
        "ddp",
        argv=[
            "--synthetic-data", "--limit-examples", "256",
            "--batch-size", "64", "--epoch", "2", "--eval-step", "100",
            "--lr", "0.05", "--no-progress", "--save-last-min-secs", "0",
            "--ckpt-path", str(tmp_path), *extra,
        ],
    )


def test_trainer_shard_optim_e2e_and_ckpt_roundtrip(tmp_path):
    """The full train stack under --shard-optim --grad-comms int8: the
    carried optimizer state is genuinely data-sharded, the comms/* gauges
    ride the metrics stream, run_start names the flags — and the
    checkpoint round-trips onto a run with BOTH flags off (the reshard
    step: host-pytree restore re-places the state, values unchanged)."""
    hp = _hparams(tmp_path, extra=["--shard-optim", "--grad-comms", "int8"])
    t = Trainer(hp, model=TinyNet(num_classes=100))
    # opt state carried sharded between dispatches
    specs = [
        leaf.sharding.spec
        for leaf in jax.tree_util.tree_leaves(t.state.opt_state)
        if np.ndim(leaf) > 0
    ]
    assert any(_has_data(s) for s in specs)
    assert t.state.comms_residual is not None
    version = t.fit()
    saved_state = jax.device_get(
        {"params": t.state.params, "opt_state": t.state.opt_state}
    )
    t.close()
    vdir = tmp_path / f"version-{version}"
    events = [
        json.loads(line)
        for line in (vdir / "events.jsonl").read_text().splitlines()
    ]
    run_start = next(e for e in events if e["kind"] == "run_start")
    assert run_start["payload"]["shard_optim"] is True
    assert run_start["payload"]["grad_comms"] == "int8"
    gauges = [
        m
        for e in events
        if e["kind"] == "metrics"
        for m in e["payload"]["metrics"]
        if m.startswith("comms/")
    ]
    assert {"comms/wire_bits", "comms/opt_state_bytes_per_device"} <= set(gauges)

    # restore across the sharding-mode change: both flags off
    hp2 = _hparams(
        tmp_path / "plain", extra=["--resume", str(vdir / "last.ckpt")]
    )
    t2 = Trainer(hp2, model=TinyNet(num_classes=100))
    assert t2.comms is None and t2.state.comms_residual is None
    restored = jax.device_get(
        {"params": t2.state.params, "opt_state": t2.state.opt_state}
    )
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(a, b), restored, saved_state
    )
    assert t2._reshard["saved_shard_optim"] is True
    assert t2._reshard["shard_optim_changed"] is True
    t2.fit()  # one more epoch on the replicated layout runs clean
    t2.close()


# ------------------------------------------------- satellite: desync reduce


def test_partial_fingerprint_device_path_detects_single_bit_drift(dp_tp_mesh):
    """The compiled per-device reduce must be at least as sensitive as
    the host path it replaces: its wrapping-int32 bitcast checksum
    catches a SINGLE low-order-bit flip on one device of a 262k-element
    leaf — the case a float32 abs-sum would round away (and the reason
    the device path deliberately does not reuse the float
    param_fingerprint formula)."""
    mesh = dp_tp_mesh
    repl = NamedSharding(mesh, P())
    tp = NamedSharding(mesh, P(None, "model"))
    params = {
        "w": jax.device_put(
            jax.random.normal(jax.random.key(1), (64, 32)), tp
        ),
        "big": jax.device_put(
            jax.random.normal(jax.random.key(2), (1 << 18,)), repl
        ),
    }
    shardings = {"w": tp, "big": repl}
    fn = make_partial_fingerprint_fn(mesh, shardings)
    device = np.asarray(fn(params))
    assert device.shape == (4, 2, 1)  # (data, model, pipe)
    # in-sync replicas: every model column constant down the data axis
    assert not check_partial_desync(device)["mismatch"]
    # host path agrees on the in-sync verdict (different checksum, same
    # contract)
    assert not check_partial_desync(
        partial_fingerprints(params, mesh)
    )["mismatch"]
    # injected drift down a column is caught on the device matrix
    assert check_partial_desync(device, inject=True)["mismatch"]

    # real per-replica drift: ONE low bit flipped in one device's copy of
    # the "replicated" big leaf (constructed from per-device buffers, the
    # way an actual desync presents)
    base = np.asarray(jax.device_get(params["big"]), np.float32)
    drift = base.copy()
    drift.view(np.int32)[12345] ^= 1  # 1 ulp
    bufs = [
        jax.device_put(drift if i == 5 else base, d)
        for i, d in enumerate(mesh.devices.flat)
    ]
    params["big"] = jax.make_array_from_single_device_arrays(
        base.shape, repl, bufs
    )
    verdict = check_partial_desync(np.asarray(fn(params)))
    assert verdict["mismatch"], "single-bit replica drift went undetected"


# ---------------------------------------------- satellite: --compute drain


def _compile_event(name, fp, **payload):
    return {
        "v": 1, "run_id": "r", "attempt": 0, "process_index": 0,
        "t_wall": 1.0, "t_mono": 1.0, "kind": "compile",
        "payload": {
            "name": name, "fingerprint": fp, "compile_s": 0.5,
            "cache": "miss", "compiles_of_fingerprint": 1,
            "recompile_after_warmup": False, "platform": "tpu",
            "device_kind": "TPU v4", "devices": 4, "flops": 1e12,
            "peak_bytes": 2 << 30, **payload,
        },
    }


def _exec_flush(name, fp, count, total_s):
    reg = MetricRegistry()
    h = reg.histogram(f"exec/{name}:{fp[:8]}/dispatch_s")
    for _ in range(count):
        h.record(total_s / count)
    return {
        "v": 1, "run_id": "r", "attempt": 0, "process_index": 0,
        "t_wall": 2.0, "t_mono": 2.0, "kind": "metrics",
        "payload": {"metrics": reg.snapshot(reset=False)},
    }


def _metrics_flush(values: dict):
    reg = MetricRegistry()
    for name, total in values.items():
        reg.histogram(name).record(total)
    return {
        "v": 1, "run_id": "r", "attempt": 0, "process_index": 0,
        "t_wall": 3.0, "t_mono": 3.0, "kind": "metrics",
        "payload": {"metrics": reg.snapshot(reset=False)},
    }


def test_compute_summary_folds_compute_drain():
    """The epoch-final chunk drains inside the metrics fetch: its device
    time lands in step/compute_s, not a dispatch span.  --compute folds
    that span into the MFU denominator pro-rata, so measured MFU stops
    overcounting."""
    fp = "aabbccddeeff0011"
    events = [
        _compile_event("chunk_runner", fp),
        _exec_flush("chunk_runner", fp, count=10, total_s=10.0),
        _metrics_flush({"step/compute_s": 5.0}),
    ]
    comp = run_report.compute_summary(events)
    (row,) = comp["rows"]
    assert row["drain_s"] == pytest.approx(comp["totals"]["drain_s"], rel=1e-6)
    assert comp["totals"]["drain_s"] == pytest.approx(5.0, rel=0.05)
    span = row["dispatch_s"] + row["drain_s"]
    assert row["mfu"] == pytest.approx(
        1e12 * 10 / span / (275e12 * 4), rel=1e-6
    )
    assert "drain folded" in run_report.format_compute(comp)


# ----------------------------------------------------------- config flags


def test_config_comms_flags():
    hp = load_config("ddp", argv=["--shard-optim", "--grad-comms", "int8"])
    assert hp.shard_optim is True and hp.grad_comms == "int8"
    hp = load_config("ddp", argv=[])
    assert hp.shard_optim is False and hp.grad_comms == "fp32"
    with pytest.raises(SystemExit):
        load_config("ddp", argv=["--grad-comms", "fp8"])

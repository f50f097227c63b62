"""The names a device trace reads (PERF.md §3): the scopes of the step
(``augment``, ``loss``, ``guards``, ``optimizer``; ``attn``, ``mlp``,
``attention`` in a ViT block) beside flax's module scopes and JAX's
``jvp`` / ``transpose`` wrappers, and one name per compiled program.

Scopes and a function's name are metadata: they change what a trace can
tell apart and nothing a program computes.  So the names are read from the
compiled HLO's ``op_name``s (at batch 8 a ResNet-18 step compiles in a few
seconds here; the lowered text will not do, because only the compiler's
inlining gives an instruction inside a ``scan`` body its whole path), by
the benchmark's own reader (``benchmark/harness/scopes.py``: ``phase_of``,
``under``), and one case pins that a step's outputs are bitwise those of
the same step with ``jax.named_scope`` a no-op.
"""

from __future__ import annotations

import contextlib
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_training_comparison_tpu import obs
from distributed_training_comparison_tpu.config import load_config
from distributed_training_comparison_tpu.data import synthetic_dataset
from distributed_training_comparison_tpu.models import get_model
from distributed_training_comparison_tpu.parallel import (
    make_mesh,
    replicated_sharding,
)
from distributed_training_comparison_tpu.train import (
    Trainer,
    configure_optimizers,
    create_train_state,
    make_chunk_runner,
    make_device_chunk_runner,
    make_eval_runner,
    make_eval_step,
    make_train_step,
)

from test_train import HP, TinyNet

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "benchmark"))
from harness import scopes  # noqa: E402

BATCH = 8
STEP_SCOPES = ("augment", "loss", "guards", "optimizer")
VIT_SCOPES = ("attn", "mlp", "attention")


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(1, backend="ddp")


def _abstract_state(model):
    tx, _ = configure_optimizers(HP, steps_per_epoch=4)
    return jax.eval_shape(
        lambda: create_train_state(model, jax.random.key(0), tx)
    )


def _spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _compiled(mesh, model_name: str, program: str):
    """``make_train_step`` or ``make_device_chunk_runner`` compiled for a
    zoo model in bf16 at a small batch, from shapes alone."""
    state = _abstract_state(get_model(model_name, dtype=jnp.bfloat16))
    key = jax.eval_shape(lambda: jax.random.key(0))
    if program == "train_step":
        fn = make_train_step(mesh, precision="bf16")
        args = (state, _spec((BATCH, 32, 32, 3), jnp.uint8),
                _spec((BATCH,), jnp.int32), key)
    else:
        fn = make_device_chunk_runner(
            mesh, BATCH, 2, precision="bf16", donate=False
        )
        args = (state, _spec((4 * BATCH, 32, 32, 3), jnp.uint8),
                _spec((4 * BATCH,), jnp.int32), key,
                _spec((), jnp.int32), _spec((), jnp.int32))
    return fn.lower(*args).compile()


_OP_NAMES: dict = {}


def _op_names(mesh, model_name: str, program: str) -> list:
    """One ``op_name`` per HLO instruction of the compiled program (``""``
    for an instruction without one), parameters and constants left out:
    they are no device op."""
    if (model_name, program) not in _OP_NAMES:
        text = _compiled(mesh, model_name, program).as_text()
        names = []
        for line in text.splitlines():
            m = re.match(r"\s+(?:ROOT )?\S+ = \S+ ([a-z\-]+)\(", line)
            if m and m.group(1) not in ("parameter", "constant"):
                got = re.search(r'op_name="([^"]*)"', line)
                names.append(got.group(1) if got else "")
        _OP_NAMES[model_name, program] = names
    return _OP_NAMES[model_name, program]


CASES = [
    (model, program)
    for model in ("resnet18", "vit_small")
    for program in ("train_step", "device_chunk_runner")
]


@pytest.mark.parametrize("model_name,program", CASES)
@pytest.mark.parametrize("scope", STEP_SCOPES)
def test_step_scope_is_a_path_component(mesh, model_name, program, scope):
    names = _op_names(mesh, model_name, program)
    assert any(scopes.under(n, scope) for n in names), scope


@pytest.mark.parametrize("program", ("train_step", "device_chunk_runner"))
@pytest.mark.parametrize("scope", VIT_SCOPES)
def test_vit_block_scopes(mesh, program, scope):
    names = _op_names(mesh, "vit_small", program)
    assert any(scopes.under(n, scope) for n in names)
    # attention sits inside the block's attention half, not beside it
    if scope == "attention":
        inside = [n for n in names if scopes.under(n, "attention")]
        assert all(scopes.under(n, "attn") for n in inside)
        assert not any(scopes.under(n, "mlp") for n in inside)


@pytest.mark.parametrize(
    "model_name,scope", [("resnet18", "stage1_block0"), ("vit_small", "attention")]
)
@pytest.mark.parametrize("program", ("train_step", "device_chunk_runner"))
def test_forward_and_backward_under_a_scope(mesh, model_name, program, scope):
    """JAX's own wrappers split a scope's ops: the same flax module (or
    named scope) holds ``jvp(...)`` instructions and
    ``transpose(jvp(...))`` ones."""
    phases = {
        scopes.phase_of(n)
        for n in _op_names(mesh, model_name, program)
        if scopes.under(n, scope)
    }
    assert {"forward", "backward"} <= phases


# the share of named instructions that may fall in no phase.  A step's own
# are a few percent; the runner adds the epoch's permutation, the key tables
# and the scan's carry, which are *other* by definition: many small
# instructions (a fifth of the ViT runner's) and, on the chip, 1-3 % of the
# time (PERF.md §5)
OTHER_LIMIT = {"train_step": 0.10, "device_chunk_runner": 0.25}


@pytest.mark.parametrize("model_name,program", CASES)
def test_few_instructions_are_unscoped(mesh, model_name, program):
    named = [n for n in _op_names(mesh, model_name, program) if n]
    phases = [scopes.phase_of(n) for n in named]
    assert len(named) > 500
    assert phases.count("other") < OTHER_LIMIT[program] * len(named), (
        [n for n in named if scopes.phase_of(n) == "other"][:20]
    )
    assert phases.count("update") > 0 and phases.count("backward") > 0


# --------------------------------------------- a token decoder's step program

QWEN3_NEXT_SCOPES = (
    "embed", "gdn", "gdn_conv", "gdn_scan", "gdn_gate_norm", "attn",
    "attn_gate", "attention", "moe", "moe_gmm", "shared_expert", "lm_head",
    "loss", "guards", "optimizer",
)
NEMOTRON_H_SCOPES = (
    "embed", "mamba", "ssm_conv", "ssd_scan", "ssm_gate_norm", "attn",
    "attention", "moe", "moe_gmm", "shared_expert", "lm_head",
    "loss", "guards", "optimizer",
)
TOKEN_CUTS = {
    "qwen3_next_tiny": "layers=4,experts=4,first_expert=0,vocab=256",
    "nemotron_h_tiny": "layers=7,experts=4,first_expert=0,vocab=256",
}
_TOKEN_OP_NAMES: dict = {}


def _token_op_names(mesh, name="qwen3_next_tiny") -> list:
    """``op_name``s of ``make_train_step`` lowered (not compiled: the names
    are the lowering's) for a tiny token decoder in bf16 under ``--remat``."""
    if name not in _TOKEN_OP_NAMES:
        model = get_model(
            name, dtype=jnp.bfloat16, remat=True, model_cut=TOKEN_CUTS[name]
        )
        state = _abstract_state(model)
        tokens = _spec((2, 32), jnp.int32)
        fn = make_train_step(mesh, precision="bf16", augment=False)
        text = fn.lower(
            state, tokens, tokens, jax.eval_shape(lambda: jax.random.key(0))
        ).as_text(debug_info=True)
        # the name stacks, not the call sites' function names beside them
        _TOKEN_OP_NAMES[name] = sorted(set(re.findall(r'loc\("(jit\([^"]*)"', text)))
    return _TOKEN_OP_NAMES[name]


@pytest.mark.parametrize("scope", QWEN3_NEXT_SCOPES)
def test_qwen3_next_scopes_are_in_the_lowered_step_program(mesh, scope):
    """``models/qwen3_next.py``'s docstring names them; the readers under
    ``benchmark/layer_metrics`` (``gdn_ms_per_step``, ``gdn_scan_ms_per_step``,
    ``gdn_scan_roofline_pct``) look for them as path components."""
    names = _token_op_names(mesh)
    mine = [n for n in names if scopes.under(n, scope)]
    assert mine, scope
    if scope.startswith("gdn"):
        # the DeltaNet mixer is no attention: ``attention_ms_per_step``
        # reads that name, and the three scopes inside sit inside ``gdn``
        assert not any(scopes.under(n, "attention") for n in mine)
        assert all(scopes.under(n, "gdn") for n in mine)
        assert not any(scopes.under(n, "layers_3") for n in mine)
    if scope in ("attn", "attention", "attn_gate"):
        assert all(scopes.under(n, "layers_3") for n in mine)
    if scope not in ("embed", "guards", "optimizer", "loss"):
        assert {"forward", "backward"} <= {scopes.phase_of(n) for n in mine}


@pytest.mark.parametrize("scope", NEMOTRON_H_SCOPES)
def test_nemotron_h_scopes_are_in_the_lowered_step_program(mesh, scope):
    """``models/nemotron_h.py``'s docstring names them; the readers under
    ``benchmark/layer_metrics`` (``mamba_ms_per_step``,
    ``ssd_scan_ms_per_step``, ``ssd_scan_roofline_pct``) look for them as
    path components.  Layers 0, 2, 4 are Mamba-2, 5 attention, 1, 3, 6
    experts: one mixer a layer."""
    names = _token_op_names(mesh, "nemotron_h_tiny")
    mine = [n for n in names if scopes.under(n, scope)]
    assert mine, scope
    layers = {i for n in mine for i in range(7) if scopes.under(n, f"layers_{i}")}
    if scope in ("mamba", "ssm_conv", "ssd_scan", "ssm_gate_norm"):
        # the state-space mixer is no attention (``attention_ms_per_step``
        # reads that name) and the three scopes inside sit inside ``mamba``
        assert not any(scopes.under(n, "attention") for n in mine)
        assert all(scopes.under(n, "mamba") for n in mine)
        assert layers == {0, 2, 4}
    if scope in ("attn", "attention"):
        assert layers == {5} and all(scopes.under(n, "attn") for n in mine)
    if scope in ("moe", "moe_gmm", "shared_expert"):
        assert layers == {1, 3, 6} and all(scopes.under(n, "moe") for n in mine)
    if scope not in ("embed", "guards", "optimizer", "loss"):
        assert {"forward", "backward"} <= {scopes.phase_of(n) for n in mine}


# ------------------------------------------------------ one name a program


def _tiny_state(mesh):
    tx, _ = configure_optimizers(HP, steps_per_epoch=4)
    state = create_train_state(TinyNet(), jax.random.key(0), tx)
    return jax.device_put(state, replicated_sharding(mesh))


def _tiny_args(mesh, name):
    x, y = synthetic_dataset(64, num_classes=10, seed=0)
    x, y = jnp.asarray(x), jnp.asarray(y)
    state, key = _tiny_state(mesh), jax.random.key(1)
    zero = jnp.asarray(0)
    w = jnp.ones((64,), jnp.float32)
    return {
        "train_step": (state, x[:16], y[:16], key),
        "eval_step": (state, x[:16], y[:16], w[:16]),
        "eval_runner": (state, x, y, w),
        "chunk_runner": (state, x.reshape(4, 16, 32, 32, 3),
                         y.reshape(4, 16), key, zero),
        "device_chunk_runner": (state, x, y, key, zero, zero),
    }[name]


def _make(mesh, name, monitor):
    return {
        "train_step": lambda: make_train_step(mesh, monitor=monitor),
        "eval_step": lambda: make_eval_step(mesh, monitor=monitor),
        "eval_runner": lambda: make_eval_runner(mesh, 16, monitor=monitor),
        "chunk_runner": lambda: make_chunk_runner(
            mesh, donate=False, monitor=monitor),
        "device_chunk_runner": lambda: make_device_chunk_runner(
            mesh, 16, 2, donate=False, monitor=monitor),
    }[name]()


@pytest.fixture
def monitor_env():
    bus = obs.configure(run_id=obs.new_run_id(), persist=True)
    yield bus, obs.CompileMonitor(bus=bus, registry=obs.MetricRegistry())
    obs.reset()


def _compile_names(bus):
    return [e["payload"]["name"] for e in bus.ring_events()
            if e["kind"] == "compile"]


def _module_name(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


# the compile event's name is what it was before the programs were named:
# the benchmark finds the train program's kernel paths by it
EVENT_NAMES = {
    "train_step": "train_step", "eval_step": "eval_step",
    "eval_runner": "eval_runner", "chunk_runner": "chunk_runner",
    "device_chunk_runner": "device_chunk_runner@k2",
}


@pytest.mark.parametrize("name", sorted(EVENT_NAMES))
def test_step_program_is_named(mesh, monitor_env, name):
    bus, monitor = monitor_env
    args = _tiny_args(mesh, name)
    assert _module_name(_make(mesh, name, None).lower(*args)) == f"jit_{name}"
    _make(mesh, name, monitor)(*args)
    assert _compile_names(bus) == [EVENT_NAMES[name]]


@pytest.fixture(scope="module")
def tiny_trainer(tmp_path_factory):
    hp = load_config("tpu", argv=[
        "--synthetic-data", "--limit-examples", "640", "--batch-size", "32",
        "--epoch", "1", "--no-progress", "--seed", "7",
        "--ckpt-path", str(tmp_path_factory.mktemp("scopes")),
    ])
    trainer = Trainer(hp, model=TinyNet(num_classes=100))
    yield trainer
    trainer.close()


@pytest.mark.parametrize("name", ("state_snapshot", "param_fingerprint"))
def test_trainer_program_is_named(tiny_trainer, name):
    t = tiny_trainer
    seen = []
    t.bus.subscribe(seen.append)
    try:
        if name == "state_snapshot":
            t._snapshot_state(t.state)
            fn, args = t._snapshot_fn, (t.state,)
        else:
            t._desync_check(inject=False)
            fn, args = t._fingerprint_fn, (t.state.params,)
    finally:
        t.bus.unsubscribe(seen.append)
    # the monitor's wrapper keeps the jitted function it observes
    assert _module_name(fn._fn.lower(*args)) == f"jit_{name}"
    # the snapshot is two executions of its one program (what a best-only
    # save writes, then the rest), compiled together at the first whole save
    times = 2 if name == "state_snapshot" else 1
    assert [
        e["payload"]["name"] for e in seen if e["kind"] == "compile"
    ] == [name] * times


# -------------------------------------------------- names compute nothing


def test_scopes_change_no_value(mesh, monkeypatch):
    """The outputs of a train step, bit for bit, against the same step
    built with ``jax.named_scope`` patched to a no-op."""
    args = _tiny_args(mesh, "train_step")

    def lowered(debug_info):
        return make_train_step(mesh).lower(*args).as_text(debug_info=debug_info)

    assert ")/guards/" in lowered(True) and ")/optimizer/" in lowered(True)
    program = lowered(False)
    named = jax.device_get(make_train_step(mesh)(*args))
    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext()
    )
    assert ")/guards/" not in lowered(True)
    assert lowered(False) == program  # the same program, locations apart
    plain = jax.device_get(make_train_step(mesh)(*args))
    for a, b in zip(jax.tree_util.tree_leaves(named),
                    jax.tree_util.tree_leaves(plain), strict=True):
        assert np.array_equal(np.asarray(a), np.asarray(b), equal_nan=True)

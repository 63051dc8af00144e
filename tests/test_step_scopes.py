"""The train step and the precision switch carry the ``adapt.*`` named
scopes that a device trace is read by (``bench/scopes.py``).

A scope is ``op_name`` metadata on the compiled program's instructions; an
op belongs to its innermost ``adapt.`` scope, which may sit inside a
transform's name (``transpose(jvp(adapt.forward))/adapt.layers/...``). On
the CPU the Pallas kernels run in interpret mode, so a kernel shows as the
ops of its wrapper (``jit(fxp_matmul)``) rather than as one custom call;
the scope around it is the same.
"""
import re

import jax
import pytest

from repro.config import load_config
from repro.train import train_loop

SCOPE = re.compile(r"(?<![\w.])adapt\.([a-z_]+)")
OP_NAME = re.compile(r'op_name="([^"]*)"')
STEP_SCOPES = {"quantize", "forward", "layers", "head", "loss", "regularize",
               "accumulate", "update"}


def _cfg():
    return load_config("tiny", overrides=[
        "quant.use_pallas=true", "quant.container_dtype=int8_packed",
        "train.seq_len=32", "train.global_batch=4", "train.accum_steps=1",
        "train.remat=full"])


def _tagged(compiled):
    """[(innermost adapt scope or None, op_name)] of the compiled program."""
    names = OP_NAME.findall(compiled.as_text())
    return [((SCOPE.findall(n) or [None])[-1], n) for n in names]


@pytest.fixture(scope="module")
def shapes():
    cfg = _cfg()
    return (cfg, jax.eval_shape(lambda: train_loop.init_state(cfg)),
            jax.eval_shape(lambda: train_loop.make_batch(cfg, 0)))


@pytest.fixture(scope="module")
def step_ops(shapes):
    cfg, state, batch = shapes
    return _tagged(jax.jit(train_loop.make_train_step(cfg))
                   .lower(state, batch).compile())


@pytest.mark.parametrize("scope,marker", [
    ("quantize", "jit(sr_quantize_fused"),   # the SR-quantize kernel
    ("forward", None),                         # the embedding lookup
    ("layers", "jit(fxp_matmul)"),
    ("layers", "jit(flash_attention)"),
    ("layers", "jit(matmul_dw)"),
    ("head", "jit(fxp_matmul)"),
    ("loss", None),
    ("regularize", None),
    ("accumulate", None),
    ("update", None),
])
def test_scope_tags_its_work(step_ops, scope, marker):
    hits = [n for s, n in step_ops
            if s == scope and (marker is None or marker in n)]
    assert hits, f"no op of adapt.{scope} ({marker})"


def test_step_carries_only_the_step_scopes(step_ops):
    assert {s for s, _ in step_ops} - {None} == STEP_SCOPES


def test_backward_ops_keep_their_scope(step_ops):
    """The transposed forward and loss land in the same scopes."""
    assert any(s == "layers" and "transpose(" in n for s, n in step_ops)
    assert any(s == "loss" and "transpose(" in n for s, n in step_ops)


def test_switch_program_carries_switch_scope(shapes):
    cfg, state, _ = shapes
    ops = _tagged(jax.jit(train_loop.make_precision_switch(cfg))
                  .lower(state).compile())
    assert {s for s, _ in ops} - {None} == {"switch"}
    assert any(s == "switch" and "edf_ladder" in n for s, n in ops)

"""The scopes that cut the compiled step's second phase
(``optim/distributed_optimizer.py``, ``sched/execute.py``):

    hvd_reduce_and_update
      hvd_exchange > wire_out | hvd_sched_bucket<i>_... | wire_in
      hvd_update
      hvd_accumulate      (backward_passes_per_step > 1)

Every instruction of the phase lies under exactly one of the three
children, with ``TrainStep`` or without it; the wire's casts lie under
``wire_out`` / ``wire_in``; and the scopes are names only: the optimized
program with its metadata dropped is the same text as with the scopes
patched out.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd

CHILDREN = ("hvd_exchange", "hvd_update", "hvd_accumulate")
NEW = CHILDREN + ("wire_out", "wire_in")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?[\w.\-]+ = (\S+) ([\w\-]+)\(")

X = jnp.ones((16, 4))
Y = jnp.ones((16, 1))


def _params():
    return {"w": jnp.full((4, 8), 0.3), "v": jnp.full((8, 1), 0.1)}


def _loss(p, b):
    x, y = b
    return jnp.mean((jnp.tanh(x @ p["w"]) @ p["v"] - y) ** 2)


def _stateful_loss(p, model_state, b):
    return _loss(p, b), {"seen": model_state["seen"] + 1.0}


def _tx(wire, k):
    return hvd.DistributedOptimizer(
        optax.adam(0.1), compression=getattr(hvd.Compression, wire),
        backward_passes_per_step=k)


def _lowered_train_step(wire, k, stateful):
    """The lowering of the step ``TrainStep`` would compile."""
    step = hvd.distributed_train_step(
        _stateful_loss if stateful else _loss, _tx(wire, k),
        stateful=stateful)
    params = _params()
    state = step.init(params)
    fn = step._build_step(step._state_specs(state))
    fn = getattr(fn, "_fn", fn)  # under the profiling plane's executor
    model_state = {"seen": jnp.zeros(())} if stateful else None
    return fn.lower(params, model_state, state, (X, Y))


def _lowered_plain_jit(wire, k):
    """A user's own ``jit`` around ``DistributedOptimizer.update`` and
    nothing else (applying the updates is then the user's own line)."""
    tx = _tx(wire, k)
    params = _params()

    def update(grads, state, params):
        return tx.update(grads, state, params)

    state = tx.init(params)
    fn = jax.jit(jax.shard_map(
        update, mesh=hvd.mesh(), in_specs=(P(), P(), P()),
        out_specs=(P(), P()), check_vma=False))
    return fn.lower(params, state, params)


def _named_instructions(lowered):
    """(opcode, result shape, op_name) of every instruction of the
    lowered program that carries a scope path.  The body of a nested
    ``jit`` (optax's ``_where``, ``remainder``) is named from its own
    root; it counts with the ``call`` that runs it, which carries the
    scope, and is left out here."""
    out, called, computation = [], set(), ""
    for line in lowered.as_text(dialect="hlo", debug_info=True).splitlines():
        if line.endswith("{") and " = " not in line:
            computation = line.replace("ENTRY ", "").split()[0]
            continue
        name = _OP_NAME.search(line)
        ins = _INSTRUCTION.match(line)
        if not (name and ins):
            continue
        if ins.group(2) == "call":
            called.add(re.search(r"to_apply=%?([\w.\-]+)", line).group(1))
        out.append((computation, ins.group(2), ins.group(1), name.group(1)))
    return [i[1:] for i in out if i[0] not in called]


def _is_the_choice_itself(opcode, shape, path):
    """``lax.cond`` between a step and no step: the conditional, the tuples
    around it and its predicate's cast to an index belong to neither
    branch."""
    leaf = path.rsplit("/", 1)[-1]
    return leaf == "cond" or (
        leaf == "convert_element_type" and opcode == "convert"
        and shape == "s32[]")


def _check_phase(instructions, wire, k):
    assert instructions
    seen = set()
    for opcode, shape, path in instructions:
        under = [c for c in CHILDREN if f"/{c}/" in f"/{path}/"]
        if k > 1 and not under and _is_the_choice_itself(opcode, shape, path):
            continue
        assert len(under) == 1, (opcode, path)
        seen.add(under[0])
        inside_wire = "/wire_out/" in path or "/wire_in/" in path
        if inside_wire:
            assert under == ["hvd_exchange"], path
        if under == ["hvd_exchange"]:
            # nothing of the exchange is left bare: the wire out, a
            # bucket, or the wire in
            assert inside_wire or "/hvd_sched_bucket" in path, path
    expected = {"hvd_exchange", "hvd_update"} | (
        {"hvd_accumulate"} if k > 1 else set())
    assert seen == expected
    casts = [(shape, path) for opcode, shape, path in instructions
             if opcode == "convert" and "convert_element_type" in path
             and shape.startswith(("bf16", "f32")) and "[]" not in shape]
    wire_casts = [p for _, p in casts
                  if "/wire_out/" in p or "/wire_in/" in p]
    if wire == "bf16":
        # two leaves out, two leaves in
        assert len([p for p in wire_casts if "/wire_out/" in p]) == 2
        assert len([p for p in wire_casts if "/wire_in/" in p]) == 2
    else:
        assert not wire_casts


@pytest.mark.parametrize("stateful", [False, True],
                         ids=["plain", "stateful"])
@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("wire", ["none", "bf16"])
def test_train_steps_second_phase_lies_under_its_children(
        hvd_module, wire, k, stateful):
    instructions = _named_instructions(
        _lowered_train_step(wire, k, stateful))
    phase = [i for i in instructions if "hvd_reduce_and_update" in i[2]]
    _check_phase(phase, wire, k)
    # and nothing of the children leaks out of the phase
    assert all("hvd_reduce_and_update" in path
               for _, _, path in instructions
               if any(f"/{c}/" in f"/{path}/" for c in CHILDREN))


@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("wire", ["none", "bf16"])
def test_a_plain_jit_of_the_optimizer_gets_the_same_children(
        hvd_module, wire, k):
    instructions = [
        i for i in _named_instructions(_lowered_plain_jit(wire, k))
        if i[0] not in ("parameter", "custom-call", "tuple",
                        "get-tuple-element")
        and not i[2].endswith("shard_map")]
    _check_phase(instructions, wire, k)


# ------------------------------------------------- names and nothing else
def _without_metadata(text):
    """An optimized program's text with every ``op_name``, source location
    and the tables they index dropped."""
    kept = []
    for line in text.splitlines():
        s = line.strip()
        if s in ("FileNames", "FunctionNames", "FileLocations",
                 "StackFrames") or re.match(r"\d+ [\"{]", s):
            continue
        kept.append(re.sub(r",? ?metadata=\{[^}]*\}", "", line))
    return "\n".join(kept)


@contextlib.contextmanager
def _scopes_patched_out(monkeypatch):
    real = jax.named_scope

    def filtered(name):
        return contextlib.nullcontext() if name in NEW else real(name)

    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", filtered)
        yield


@pytest.mark.parametrize("lower", [
    pytest.param(lambda wire, k: _lowered_train_step(wire, k, False),
                 id="train_step"),
    pytest.param(lambda wire, k: _lowered_train_step(wire, k, True),
                 id="train_step_stateful"),
    pytest.param(_lowered_plain_jit, id="plain_jit"),
])
@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("wire", ["none", "bf16"])
def test_the_scopes_are_names_and_nothing_else(
        hvd_module, monkeypatch, lower, wire, k):
    with_scopes = lower(wire, k).compile().as_text()
    assert "hvd_update" in with_scopes and "hvd_exchange" in with_scopes
    with _scopes_patched_out(monkeypatch):
        without = lower(wire, k).compile().as_text()
    assert "hvd_update" not in without and "hvd_exchange" not in without
    assert "hvd_sched_bucket" in without  # that scope was there before
    assert _without_metadata(with_scopes) == _without_metadata(without)

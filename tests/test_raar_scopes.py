"""The RAAR step's phase scopes (``apps/ptycho/solver.py``): every op of the
compiled step sits in one of the six ``raar/<phase>`` scopes, and the
scopes are metadata only, so the compiled program is the same op for op
and fusion for fusion without them. Compiled on the CPU at a small size,
on the three paths that call ``raar_step``: a Python iteration number (the
examples), a traced one (the benchmark and the streaming loop), and a
rank's step under ``shard_map`` with the ``psum`` of the bridge."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.ptycho import solver
from repro.apps.ptycho.solver import SolverConfig, raar_step
from repro.core import MPIBridge
from repro.utils import make_mesh

PHASES = ("far_field", "modulus", "object_solve", "probe_solve",
          "exit_waves", "combine")
EXEMPT = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
F, N, OBJ = 16, 16, 48


def _inputs():
    rng = np.random.default_rng(0)
    psi = (rng.standard_normal((F, N, N))
           + 1j * rng.standard_normal((F, N, N))).astype(np.complex64)
    pos = rng.integers(0, OBJ - N, (F, 2)).astype(np.int32)
    return (jnp.asarray(psi), jnp.asarray(np.abs(psi), jnp.float32),
            jnp.asarray(pos), jnp.asarray(psi[0]))


def _compiled_text(path: str) -> str:
    psi, mag, pos, probe = _inputs()
    cfg = SolverConfig()
    if path == "python_iteration":
        step = jax.jit(lambda *a: raar_step(*a, (OBJ, OBJ), cfg, 5))
        return step.lower(psi, mag, pos, probe).compile().as_text()
    if path == "traced_iteration":
        step = jax.jit(lambda *a: raar_step(*a[:4], (OBJ, OBJ), cfg, a[4]))
        return step.lower(psi, mag, pos, probe,
                          jnp.int32(5)).compile().as_text()
    bridge = MPIBridge(mesh=make_mesh((1,), ("workers",)))

    def rank(psi, mag, pos, probe):
        out = raar_step(psi[0], mag[0], pos[0], probe[0], (OBJ, OBJ), cfg,
                        5, axis_name=bridge.axis_name)
        return tuple(x[None] for x in out)

    return bridge.spmd(rank).lower(
        psi[None], mag[None], pos[None], probe[None]).compile().as_text()


def _entry(text: str) -> list[tuple[str, str, str | None]]:
    """``(name, opcode, op_name)`` of each instruction of the entry
    computation."""
    body = text[text.index("\nENTRY") + 1:]
    body = body[:body.index("\n}")]
    out = []
    for line in body.splitlines()[1:]:
        name, _, rest = line.strip().removeprefix("ROOT ").partition(" = ")
        if rest.startswith("("):              # a tuple shape: skip it whole
            depth = 0
            for i, ch in enumerate(rest):
                depth += {"(": 1, ")": -1}.get(ch, 0)
                if depth == 0:
                    rest = rest[i + 1:]
                    break
        else:
            rest = rest.split(" ", 1)[1]
        opcode = re.match(r"\s*([a-z][a-z0-9-]*)\(", rest).group(1)
        op_name = re.search(r'op_name="([^"]*)"', line)
        out.append((name, opcode, op_name.group(1) if op_name else None))
    return out


def _body(text: str) -> str:
    """The computations of a compiled program with their op metadata taken
    out: what is left is its ops, their operands and its fusions."""
    text = text[re.search(r"^(%|ENTRY)", text, re.M).start():]
    return re.sub(r", metadata=\{[^}]*\}", "", text)


PATHS = ["python_iteration", "traced_iteration", "shard_map"]


@pytest.mark.parametrize("path", PATHS)
def test_every_entry_op_is_in_a_raar_phase(path):
    scoped = [f"raar/{p}/" for p in PHASES]
    entry = _entry(_compiled_text(path))
    assert len(entry) > 10
    unscoped = [(name, opcode, op_name) for name, opcode, op_name in entry
                if opcode not in EXEMPT
                and not any(s in (op_name or "") for s in scoped)]
    assert not unscoped, unscoped
    # the step's main ops land in the phase that owns them
    phase_of = {opcode: op_name for _, opcode, op_name in entry}
    assert "raar/far_field/" in phase_of["fft"]


@pytest.mark.parametrize("path", PATHS)
def test_scopes_leave_the_compiled_program_unchanged(path, monkeypatch):
    scoped = _compiled_text(path)
    monkeypatch.setattr(solver, "_phase",
                        lambda name: contextlib.nullcontext())
    plain = _compiled_text(path)
    in_phase = re.compile(r'op_name="[^"]*/raar/')
    assert in_phase.search(scoped) and not in_phase.search(plain)
    assert _body(scoped) == _body(plain)


def test_entry_parser_reads_tuple_shapes():
    text = ("HloModule m\n\nENTRY %main (a: f32[2]) -> (f32[2], f32[2]) {\n"
            "  %a = f32[2]{0} parameter(0), metadata={op_name=\"a\"}\n"
            "  %f = (f32[2]{0:T(8)}, f32[2]{0}) fusion(%a, %a), kind=kLoop,"
            " metadata={op_name=\"jit(f)/raar/combine/add\"}\n"
            "  ROOT %t = (f32[2]{0}, f32[2]{0}) tuple(%a, %a)\n}\n")
    assert _entry(text) == [("%a", "parameter", "a"),
                            ("%f", "fusion", "jit(f)/raar/combine/add"),
                            ("%t", "tuple", None)]

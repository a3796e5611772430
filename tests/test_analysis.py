"""The dispatch-hygiene analyzer: every rule catches its seeded-violation
fixture, stays silent on the clean twin, suppressions work, and the real
tree is clean (the CI gate's contract).

The analyzer is pure stdlib — these tests never import jax, so they run
on the bare tier too.
"""
import json
import subprocess
import sys
from pathlib import Path

import pytest

from repro.analysis.analyzer import analyze_sources, run
from repro.analysis.rules import ALL_RULES

REPO = Path(__file__).resolve().parent.parent
SRC = REPO / "src"

# -- fixtures: (rule, bad source, expected minimum hits, clean twin) ---------

R1_BAD = """\
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def step(dist):
    total = float(dist.sum())
    host = np.asarray(dist)
    n = dist.item()
    if jnp.any(dist > 0):
        dist = dist + 1
    return dist + total + host + n

@jax.jit
def outer(x):
    return helper(x)

def helper(x):
    return x.item()
"""

R1_CLEAN = """\
import jax
import jax.numpy as jnp
import numpy as np

@jax.jit
def step(dist):
    m = dist.shape[0]
    k = int(dist.ndim)
    dist = jnp.where(dist > 0, dist + 1.0, dist)
    return jax.lax.cond(m > 2, lambda d: d, lambda d: d * 1.0, dist)

def host_prep(x):
    # outside the jit boundary: numpy is the POINT here (arg staging)
    return np.asarray(x)
"""

R2_BAD = """\
import functools

@functools.lru_cache(maxsize=None)
def step_fns(mesh, q_axes):
    return q_axes

def grow(n):
    f_cap = n + 3
    q_cap = 100
    ell_cap = n + 5
    dist_cap = n + 7
    fns = step_fns(1, [1, 2])
    return f_cap, q_cap, ell_cap, dist_cap, fns
"""

R2_CLEAN = """\
import functools

def _next_pow2(n):
    return 1 << (max(1, n) - 1).bit_length()

@functools.lru_cache(maxsize=None)
def step_fns(mesh, q_axes):
    return q_axes

def grow(n, dist):
    f_cap = _next_pow2(n)
    f_cap *= 2
    q_cap = dist.shape[0]
    ell_cap = _next_pow2(n)
    spill_cap = ell_cap
    spill_cap *= 2
    dist_cap = _next_pow2(n)
    dist_ovf_cap = min(dist_cap, 4096)
    fns = step_fns(1, (1, 2))
    return f_cap, q_cap, ell_cap, spill_cap, dist_cap, dist_ovf_cap, fns
"""

R3_BAD = """\
from jax.experimental import pallas as pl

_OFFSET = 2

def lower(x):
    return pl.BlockSpec((128, 128), lambda i, j: (i + _OFFSET, j))
"""

R3_CLEAN = """\
from jax.experimental import pallas as pl
from ..maxmin.maxmin import pick_block_sizes

def lower(x, m, n, k):
    bm, bk, bn = pick_block_sizes(m, k, n)
    return pl.BlockSpec((1, bm, bn), lambda i, j: (0, i, j))
"""

R4_BAD = """\
class ContractionBackend:
    zero = 0.0
    exact = True

    def contract(self, d, a):
        raise NotImplementedError

    def contract_rows(self, d_s, a_l):
        raise NotImplementedError

    def contract_batched(self, dist, adj, btt, mask):
        return dist

    def prepare_state(self, dist, adj):
        return dist, adj

    def decode_state(self, dist):
        return dist


class HalfBackend(ContractionBackend):
    def contract(self, d, a):
        return d


def use(make_engine, resolve_backend):
    resolve_backend("palas")
    return make_engine(backend="palas")
"""

R4_CLEAN = """\
class ContractionBackend:
    zero = 0.0
    exact = True

    def contract(self, d, a):
        raise NotImplementedError

    def contract_rows(self, d_s, a_l):
        raise NotImplementedError

    def contract_batched(self, dist, adj, btt, mask):
        return dist

    def prepare_state(self, dist, adj):
        return dist, adj

    def decode_state(self, dist):
        return dist


class FullBackend(ContractionBackend):
    def contract(self, d, a):
        return d

    def contract_rows(self, d_s, a_l):
        return d_s


def use(make_engine, resolve_backend):
    resolve_backend("pallas")
    return make_engine(backend="jnp")
"""

R5_BAD = """\
import numpy as np

class Engine:
    def drain(self, pending):
        while pending:
            h = pending.pop(0)
        return h

    def requeue(self, pending, h):
        pending.insert(0, h)

    def telemetry(self, arrays, shard_rounds):
        t = float(arrays.now)
        r = np.asarray(shard_rounds)
        return t, r
"""

R5_CLEAN = """\
import numpy as np
import jax

class Engine:
    def drain(self, pending):
        while pending:
            h = pending.popleft()
        return h

    def _flush_counts(self, shard_rounds):
        return np.asarray(shard_rounds)

    def _flush_health(self, overflow_counts):
        # the supervisor's per-interval telemetry flush is a sanctioned
        # site, same as the executor counter flushes
        return np.asarray(overflow_counts)

    def restore(self, state):
        return float(np.asarray(jax.device_get(state.now)))
"""

FIXTURES = {
    "R1": (R1_BAD, 5, R1_CLEAN),
    "R2": (R2_BAD, 5, R2_CLEAN),
    "R3": (R3_BAD, 3, R3_CLEAN),
    "R4": (R4_BAD, 3, R4_CLEAN),
    "R5": (R5_BAD, 4, R5_CLEAN),
}

# fixture files live under a kernels/ dir so R3's path scoping applies
FIXTURE_RELPATH = "src/fake/kernels/fixture.py"


def _hits(source, rule):
    findings = analyze_sources({FIXTURE_RELPATH: source}, rules=[rule])
    return [f for f in findings if f.rule == rule]


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_rule_catches_seeded_fixture(rule):
    bad, n_min, _clean = FIXTURES[rule]
    hits = _hits(bad, rule)
    assert len(hits) >= n_min, (
        f"{rule} found {len(hits)} of >= {n_min} seeded violations:\n"
        + "\n".join(f.format() for f in hits))


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_rule_silent_on_clean_twin(rule):
    _bad, _n, clean = FIXTURES[rule]
    hits = _hits(clean, rule)
    assert not hits, "\n".join(f.format() for f in hits)


def test_r1_reaches_through_helper_calls():
    hits = _hits(R1_BAD, "R1")
    assert any("helper" in f.message for f in hits), (
        "the .item() in the un-decorated helper must be reached through "
        "the jitted caller")


def test_r1_flags_telemetry_in_traced_code():
    src = """\
import jax
from repro import telemetry

@jax.jit
def step(x):
    with telemetry.span("step"):
        return x + 1

def host(x):
    with telemetry.span("host"):
        return step(x)
"""
    hits = _hits(src, "R1")
    assert len(hits) == 1 and "telemetry.span" in hits[0].message
    assert "`step`" in hits[0].message


def test_r1_ignores_host_side_numpy():
    hits = _hits(R1_CLEAN + "\n", "R1")
    assert not hits  # host_prep's np.asarray is outside the jit boundary


def test_noqa_suppresses_but_still_reports():
    src = R5_BAD.replace(
        "h = pending.pop(0)",
        "h = pending.pop(0)  # repro: noqa[R5]")
    findings = analyze_sources({FIXTURE_RELPATH: src}, rules=["R5"])
    popfinds = [f for f in findings if "pop(0)" in f.message]
    assert popfinds and all(f.suppressed for f in popfinds)
    assert any(not f.suppressed for f in findings)  # the others still fail


def test_bare_noqa_suppresses_all_rules():
    src = "def f(n):\n    f_cap = n + 3  # repro: noqa\n    return f_cap\n"
    findings = analyze_sources({"m.py": src})
    assert findings and all(f.suppressed for f in findings)


def test_whole_repo_is_clean():
    findings, n_files = run([str(SRC)])
    live = [f for f in findings if not f.suppressed]
    assert n_files > 40
    assert not live, "\n".join(f.format() for f in live)


def test_rule_registry_complete():
    assert sorted(m.RULE for m in ALL_RULES) == ["R1", "R2", "R3", "R4", "R5"]
    for m in ALL_RULES:
        assert m.TITLE


def test_cli_exit_codes_and_json(tmp_path):
    bad = tmp_path / "kernels" / "bad.py"
    bad.parent.mkdir()
    bad.write_text(R5_BAD)
    env_src = str(SRC)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", str(bad), "--format=json"],
        capture_output=True, text=True,
        env={"PYTHONPATH": env_src, "PATH": "/usr/bin:/bin"},
    )
    assert proc.returncode == 1, proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["unsuppressed"] >= 4
    assert payload["counts_by_rule"].get("R5", 0) >= 4
    assert payload["checked_files"] == 1

    ok = subprocess.run(
        [sys.executable, "-m", "repro.analysis", str(SRC), "--format=json"],
        capture_output=True, text=True,
        env={"PYTHONPATH": env_src, "PATH": "/usr/bin:/bin"},
    )
    assert ok.returncode == 0, ok.stdout + ok.stderr
    assert json.loads(ok.stdout)["unsuppressed"] == 0

"""The invariant checker checks itself: every rule fires on known-bad code
and stays silent on known-good code, the pragma waiver works, and — the real
acceptance criterion — the checker runs clean over this repo's ``src`` and
``tests`` trees exactly as the CI ``invariants`` job invokes it.

Fixtures are source strings fed through ``check_source`` with a purpose-built
:class:`Registry` (module suffix ``fixture/mod.py``), so the rules are
exercised against declarative config rather than the repo's hard-coded
entries — the same mechanism a future cache/lock/executor would register
through.
"""

import types
from pathlib import Path

from repro.analysis import DEFAULT_REGISTRY, RULES, check_paths, check_source
from repro.analysis.api_surface import check_module
from repro.analysis.registry import (
    GuardedAttrs,
    GuardedGlobals,
    PurityConfig,
    Registry,
)

REPO = Path(__file__).resolve().parent.parent

FIXTURE_PATH = "fixture/mod.py"

FIXTURE_REGISTRY = Registry(
    guarded_globals=(
        GuardedGlobals(
            module=FIXTURE_PATH,
            names=("_CACHE",),
            guards=("_LOCK",),
            allow_in=("blessed",),
        ),
    ),
    guarded_attrs=(
        GuardedAttrs(
            module=FIXTURE_PATH,
            owner="Engine",
            attrs=("_queue",),
            guards=("_cv",),
            allow_in=("Engine.__init__", "Engine.serialised"),
        ),
    ),
)


def codes(findings):
    return [v.code for v in findings]


def run(source, *, select, registry=FIXTURE_REGISTRY):
    return check_source(source, FIXTURE_PATH, registry=registry, select=[select])


# ------------------------------------------------------------------- TRD001 --
def test_trd001_bad_unguarded_global():
    found = run(
        "def evict():\n"
        "    _CACHE.clear()\n",
        select="TRD001",
    )
    assert codes(found) == ["TRD001"]
    assert "_CACHE" in found[0].message and "_LOCK" in found[0].message


def test_trd001_bad_unguarded_attr_outside_allowlist():
    found = run(
        "class Engine:\n"
        "    def peek(self):\n"
        "        return len(self._queue)\n",
        select="TRD001",
    )
    assert codes(found) == ["TRD001"]
    assert "_queue" in found[0].message


def test_trd001_bad_guard_does_not_leak_into_nested_def():
    # The nested function runs later, after the with block exits.
    found = run(
        "def outer():\n"
        "    with _LOCK:\n"
        "        def cb():\n"
        "            return _CACHE.get(1)\n"
        "    return cb\n",
        select="TRD001",
    )
    assert codes(found) == ["TRD001"]


def test_trd001_good_with_guard():
    found = run(
        "def evict():\n"
        "    with _LOCK:\n"
        "        _CACHE.clear()\n",
        select="TRD001",
    )
    assert found == []


def test_trd001_good_allowlisted_and_module_level():
    found = run(
        "_CACHE = {}\n"  # the definition site itself is exempt
        "def blessed():\n"
        "    return _CACHE.get(1)\n"
        "class Engine:\n"
        "    def __init__(self):\n"
        "        self._queue = []\n"
        "    def serialised(self):\n"
        "        return self._queue.pop()\n",
        select="TRD001",
    )
    assert found == []


def test_trd001_silent_on_other_modules():
    found = check_source(
        "def evict():\n    _CACHE.clear()\n",
        "other/file.py",
        registry=FIXTURE_REGISTRY,
        select=["TRD001"],
    )
    assert found == []


# ------------------------------------------------------------------- TRD002 --
def test_trd002_bad_device_reuse_after_donation():
    found = run(
        "def go(plan, dl, d, du, b):\n"
        "    ex = FusedExecutor('pallas')\n"
        "    ops = jnp.asarray(d)\n"
        "    ex.execute(plan, ops, ops, ops, ops)\n"
        "    return ops.sum()\n",
        select="TRD002",
    )
    assert codes(found) == ["TRD002"]
    assert "ops" in found[0].message


def test_trd002_bad_starred_container_reuse():
    found = run(
        "def go(plan, arrays):\n"
        "    ex = FusedExecutor('pallas')\n"
        "    device_ops = [jnp.asarray(a) for a in arrays]\n"
        "    ex.execute(plan, *device_ops)\n"
        "    return device_ops[0]\n",
        select="TRD002",
    )
    assert codes(found) == ["TRD002"]


def test_trd002_bad_self_attr_executor():
    found = run(
        "class S:\n"
        "    def __init__(self):\n"
        "        self._fused = FusedExecutor('pallas')\n"
        "    def go(self, plan, d):\n"
        "        dd = jax.device_put(d)\n"
        "        self._fused.execute(plan, dd, dd, dd, dd)\n"
        "        return dd\n",
        select="TRD002",
    )
    assert codes(found) == ["TRD002"]


def test_trd002_good_numpy_operands_and_rebinding():
    found = run(
        "def go(plan, dl, d, du, b):\n"
        "    ex = FusedExecutor('pallas')\n"
        "    x, _ = ex.execute(plan, dl, d, du, b)\n"  # host operands: safe
        "    ops = jnp.asarray(d)\n"
        "    ex.execute(plan, ops, ops, ops, ops)\n"
        "    ops = jnp.asarray(d)\n"  # rebinding clears the donation
        "    return ops, x\n",
        select="TRD002",
    )
    assert found == []


def test_trd002_good_donate_false():
    found = run(
        "def go(plan, d):\n"
        "    keep = FusedExecutor('pallas', donate=False)\n"
        "    ops = jnp.asarray(d)\n"
        "    keep.execute(plan, ops, ops, ops, ops)\n"
        "    return ops\n",
        select="TRD002",
    )
    assert found == []


def test_trd002_pragma_waives_the_line():
    src = (
        "def go(plan, d):\n"
        "    ex = FusedExecutor('pallas')\n"
        "    ops = jnp.asarray(d)\n"
        "    ex.execute(plan, ops, ops, ops, ops)\n"
        "    return ops  # trd: allow[TRD002]\n"
    )
    assert run(src, select="TRD002") == []


# ------------------------------------------------------------------- TRD003 --
def test_trd003_bad_print_and_time_in_decorated_jit():
    found = run(
        "@jax.jit\n"
        "def f(x):\n"
        "    print('tracing', x)\n"
        "    t = time.perf_counter()\n"
        "    return x * t\n",
        select="TRD003",
    )
    assert codes(found) == ["TRD003", "TRD003"]


def test_trd003_bad_np_on_traced_value_via_call_site():
    found = run(
        "def f(x):\n"
        "    y = x + 1\n"
        "    return np.asarray(y)\n"
        "g = jax.jit(f)\n",
        select="TRD003",
    )
    assert codes(found) == ["TRD003"]
    assert "np.asarray" in found[0].message


def test_trd003_bad_partial_jit_and_global_mutation():
    found = run(
        "@functools.partial(jax.jit, static_argnames=('m',))\n"
        "def f(x, m):\n"
        "    global COUNT\n"
        "    COUNT += 1\n"
        "    return x\n",
        select="TRD003",
    )
    assert codes(found) == ["TRD003"]
    assert "global" in found[0].message


def test_trd003_bad_pallas_call_kernel():
    found = run(
        "def kernel(x_ref, o_ref):\n"
        "    o_ref[...] = x_ref[...] * random.random()\n"
        "def op(x):\n"
        "    return pl.pallas_call(kernel, out_shape=x)(x)\n",
        select="TRD003",
    )
    assert codes(found) == ["TRD003"]


def test_trd003_good_np_on_static_values():
    # np on trace-time constants is constant folding, not a host effect.
    found = run(
        "@jax.jit\n"
        "def f(x):\n"
        "    scale = np.float64(2.0)\n"
        "    idx = np.arange(4)\n"
        "    return x * scale + idx.sum()\n",
        select="TRD003",
    )
    assert found == []


def test_trd003_good_untraced_function_is_free():
    found = run(
        "def host_helper(x):\n"
        "    print('host side is fine')\n"
        "    return np.asarray(x)\n",
        select="TRD003",
    )
    assert found == []


def test_trd003_good_jnp_inside_trace():
    found = run(
        "@jax.jit\n"
        "def f(dl, d, du, b):\n"
        "    c = jnp.concatenate([dl, d], axis=-1)\n"
        "    return jnp.zeros_like(c) + b.sum() * du[0]\n",
        select="TRD003",
    )
    assert found == []


# ------------------------------------------------------------------- TRD005 --
def _module(name, **attrs):
    mod = types.ModuleType(name)
    for k, v in attrs.items():
        setattr(mod, k, v)
    return mod


def test_trd005_bad_unresolvable_and_undocumented():
    class Undocumented:
        pass

    mod = _module(
        "fixture_api",
        __all__=["Undocumented", "missing_name"],
        Undocumented=Undocumented,
    )
    found = check_module(mod, FIXTURE_REGISTRY)
    messages = " | ".join(v.message for v in found)
    assert codes(found) == ["TRD005", "TRD005"]
    assert "missing_name" in messages and "Undocumented" in messages


def test_trd005_bad_config_field_missing_from_docstring():
    import dataclasses

    @dataclasses.dataclass
    class SolverConfig:
        """Documented knobs: m only."""

        m: int = 10
        num_chunks: int = 1

    mod = _module(
        "fixture_api", __all__=["SolverConfig"], SolverConfig=SolverConfig
    )
    found = check_module(mod, FIXTURE_REGISTRY)
    assert codes(found) == ["TRD005"]
    assert "num_chunks" in found[0].message


def test_trd005_good_documented_surface():
    def solve(x):
        """Solve it."""
        return x

    mod = _module(
        "fixture_api",
        __all__=["solve", "LIMIT"],
        solve=solve,
        LIMIT=42,  # plain constants need no docstring
    )
    assert check_module(mod, FIXTURE_REGISTRY) == []


def test_trd005_good_real_api_surface():
    import repro.api as api

    assert check_module(api, DEFAULT_REGISTRY) == []


# ----------------------------------------------------------------- framework --
def test_syntax_error_reports_trd000():
    found = check_source("def broken(:\n", "bad.py", registry=FIXTURE_REGISTRY)
    assert codes(found) == ["TRD000"]


def test_rule_table_is_complete():
    assert sorted(RULES) == ["TRD001", "TRD002", "TRD003", "TRD005"]
    for rule in RULES.values():
        assert rule.SUMMARY and rule.FIXIT and rule.NAME


def test_cli_rejects_unknown_rule_code():
    from repro.analysis.__main__ import main

    assert main(["check", "--select", "TRD999", str(REPO / "src")]) == 2


# ------------------------------------------------------- the repo gate itself --
def test_repo_is_clean():
    """`python -m repro.analysis check src tests` — exactly what CI runs."""
    findings = check_paths([str(REPO / "src"), str(REPO / "tests")])
    assert findings == [], "\n".join(v.format() for v in findings)


def test_repo_lock_guard_rule_is_wired_to_real_files():
    """DEFAULT_REGISTRY must actually cover plan.py/api.py (guard against a
    registry path suffix drifting away from the tree and silently checking
    nothing)."""
    covered = [e.module for e in DEFAULT_REGISTRY.guarded_globals]
    covered += [e.module for e in DEFAULT_REGISTRY.guarded_attrs]
    for suffix in covered:
        assert (REPO / "src" / suffix).exists(), suffix


# ------------------------------------- TRD001: telemetry registry entries --
TELEMETRY_RING_PATH = "src/repro/telemetry/ring.py"
TELEMETRY_REFIT_PATH = "src/repro/telemetry/refit.py"


def test_trd001_telemetry_ring_bad_unguarded_touch():
    """The real DEFAULT_REGISTRY entry fires on an unguarded touch of the
    ring's window/counters outside the allowlist."""
    found = check_source(
        "class TelemetryBuffer:\n"
        "    def peek(self):\n"
        "        return len(self._ring) + self._dropped\n",
        TELEMETRY_RING_PATH,
        registry=DEFAULT_REGISTRY,
        select=["TRD001"],
    )
    assert found and set(codes(found)) == {"TRD001"}


def test_trd001_telemetry_ring_good_guarded_and_init():
    found = check_source(
        "class TelemetryBuffer:\n"
        "    def __init__(self):\n"
        "        self._ring = []\n"
        "        self._recorded = 0\n"
        "        self._dropped = 0\n"
        "    def record(self, o):\n"
        "        with self._lock:\n"
        "            self._ring.append(o)\n"
        "            self._recorded += 1\n",
        TELEMETRY_RING_PATH,
        registry=DEFAULT_REGISTRY,
        select=["TRD001"],
    )
    assert found == []


def test_trd001_refitter_bad_counter_outside_lock():
    found = check_source(
        "class OnlineRefitter:\n"
        "    def bump(self):\n"
        "        self._refits += 1\n"
        "        return self._last_heuristic\n",
        TELEMETRY_REFIT_PATH,
        registry=DEFAULT_REGISTRY,
        select=["TRD001"],
    )
    assert found and set(codes(found)) == {"TRD001"}


def test_trd001_refitter_good_under_lock():
    found = check_source(
        "class OnlineRefitter:\n"
        "    def bump(self):\n"
        "        with self._lock:\n"
        "            self._refits += 1\n"
        "            self._last_refit_t = 1.0\n",
        TELEMETRY_REFIT_PATH,
        registry=DEFAULT_REGISTRY,
        select=["TRD001"],
    )
    assert found == []


def test_default_registry_covers_telemetry_state():
    """Wiring test: the registry's telemetry entries point at real files and
    name the state those files actually guard."""
    ring = [
        e
        for e in DEFAULT_REGISTRY.guarded_attrs
        if e.module.endswith("repro/telemetry/ring.py")
    ]
    refit = [
        e
        for e in DEFAULT_REGISTRY.guarded_attrs
        if e.module.endswith("repro/telemetry/refit.py")
    ]
    assert ring and ring[0].owner == "TelemetryBuffer"
    assert set(ring[0].attrs) >= {"_ring", "_recorded", "_dropped"}
    assert refit and refit[0].owner == "OnlineRefitter"
    assert {"_refits", "_last_heuristic", "_last_latency_model"} <= set(
        refit[0].attrs
    )
    for e in ring + refit:
        assert e.guards == ("_lock",)
        assert (REPO / "src" / e.module).exists()

# ----------------------------------- TRD001/TRD002: sharded-solver entries --
PARALLEL_SOLVER_PATH = "src/repro/parallel/solver.py"


def test_trd001_mesh_cache_bad_unguarded_touch():
    """The real DEFAULT_REGISTRY entry fires when the mesh memo is touched
    outside its lock (it is populated from caller and worker threads)."""
    found = check_source(
        "def lookup(key):\n"
        "    return _MESH_CACHE.get(key)\n",
        PARALLEL_SOLVER_PATH,
        registry=DEFAULT_REGISTRY,
        select=["TRD001"],
    )
    assert found and set(codes(found)) == {"TRD001"}
    assert "_MESH_CACHE" in found[0].message


def test_trd001_mesh_cache_good_under_lock():
    found = check_source(
        "_MESH_CACHE = {}\n"  # definition site is exempt
        "def lookup(key):\n"
        "    with _MESH_LOCK:\n"
        "        return _MESH_CACHE.get(key)\n",
        PARALLEL_SOLVER_PATH,
        registry=DEFAULT_REGISTRY,
        select=["TRD001"],
    )
    assert found == []


def test_default_registry_covers_mesh_cache():
    """Wiring test: the registry names the mesh memo the real module guards."""
    entries = [
        e
        for e in DEFAULT_REGISTRY.guarded_globals
        if e.module.endswith("repro/parallel/solver.py")
    ]
    assert entries and entries[0].names == ("_MESH_CACHE",)
    assert entries[0].guards == ("_MESH_LOCK",)
    assert (REPO / "src" / entries[0].module).exists()


def test_trd002_covers_mesh_constructed_executor():
    """Donation discipline holds for the sharded path: a FusedExecutor built
    with a mesh still donates its operands (only donate=False disables), so
    reuse after a sharded execute must keep firing TRD002."""
    found = run(
        "def go(plan, d, devices):\n"
        "    ex = FusedExecutor('pallas', mesh=devices)\n"
        "    ops = jnp.asarray(d)\n"
        "    ex.execute(plan, ops, ops, ops, ops)\n"
        "    return ops.sum()\n",
        select="TRD002",
    )
    assert codes(found) == ["TRD002"]

"""Tier-1: the program-contract verifier (``stencil_tpu.analysis``).

The tentpole gate: every registered contract over the whole canonical
route × overlap × halo × storage-dtype matrix of REALLY built
programs (interpret/CPU mode) — plus the fixture corpus proving each
contract fires on a seeded violation and stays quiet on the sanctioned
pattern, the coverage-ledger pins (axis matrix AND pallas-kernel ledger),
analyzer robustness (nested loop bodies, donated buffers, the pallas
opacity/kernel-verifier split), and the static prune pins (the tune
space's zero-compile VMEM and Mosaic-legality prunes and the ladder's
prefilter descents, VMEM_OOM and COMPILE_REJECT alike).
"""

import glob
import importlib.util
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

import program_fingerprint as pfp
from stencil_tpu import analysis
from stencil_tpu.analysis import jaxpr as jx
from stencil_tpu.analysis import programs as aprog
from stencil_tpu.analysis import registry as aregistry
from stencil_tpu.analysis import vmem as avmem
from stencil_tpu.analysis.cli import main as analysis_main
from stencil_tpu.ops import stream_plan as sp

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE_DIR = os.path.join(HERE, "analysis_fixtures")
FIXTURES = sorted(glob.glob(os.path.join(FIXTURE_DIR, "*.py")))

_HEADER = re.compile(r"#\s*analysis-fixture:\s*contract=(\S+)\s+expect=(\S+)")


def _parse_header(path):
    with open(path) as fh:
        first = fh.readline()
    m = _HEADER.match(first)
    assert m, f"{path}: first line must be an analysis-fixture header"
    return m.group(1), m.group(2)


def _load(path):
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(f"afix_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.build()


# --- the gate ----------------------------------------------------------------


def test_canonical_programs_verify():
    """Every contract over every canonical program: the shipped tree's
    traced programs carry no findings.  This is the acceptance gate
    ``python -m stencil_tpu.analysis`` fronts."""
    artifacts = aprog.build_matrix()
    assert len(artifacts) == len(aprog.CANONICAL_PROGRAMS)
    findings = analysis.check_artifacts(artifacts)
    assert not findings, "\n".join(f.render() for f in findings)


@pytest.mark.parametrize("label", pfp.labels())
def test_program_fingerprint(label):
    """Every canonical program (the matrix the gate above built once, shared
    through ``build_program``'s memo) and the step or exchange program of each
    benchmark configuration, as its model builds it at a CPU size: the traced
    program is the recorded one — kernel bodies, grids, BlockSpecs, aliases,
    kernel names and scopes — wherever its source lines now sit.  A change
    that MEANS to change a program regenerates the goldens
    (``python tests/program_fingerprint.py --write``) and says which."""
    assert pfp.program_fingerprint(label) == pfp.load_goldens().get(label), (
        f"{label}: the traced program is not the recorded one"
    )


def test_registry_matches_matrix():
    """The jax-free coverage ledger (what the contract-coverage lint rule
    reads) cannot drift from the real matrix, in either direction — and
    every ledger-named vocabulary really exists in its named module."""
    covered = aprog.covered_axis_values()
    assert set(covered) == set(aregistry.CANONICAL_AXES)
    for axis, entry in aregistry.CANONICAL_AXES.items():
        assert covered[axis] == set(entry["covered"]), axis
        mod_path = entry["module"].replace("/", ".")[: -len(".py")]
        mod = __import__(mod_path, fromlist=[axis])
        declared = getattr(mod, axis)
        assert set(declared) == set(entry["covered"]), (
            f"{axis} declares {declared} but the ledger covers "
            f"{entry['covered']} — grow the canonical matrix with the axis"
        )


# --- fixture corpus: every contract fires and stays quiet --------------------


@pytest.mark.parametrize(
    "path", FIXTURES, ids=[os.path.basename(p)[:-3] for p in FIXTURES]
)
def test_fixture(path):
    if path.endswith("README.md"):
        return
    contract, expect = _parse_header(path)
    art = _load(path)
    findings = analysis.check(art, contract=contract)
    if expect == "fire":
        assert findings, f"{path}: expected {contract} to fire"
    else:
        assert not findings, "\n".join(f.render() for f in findings)


def test_every_contract_has_fire_and_clean_fixtures():
    names = {cls.name for cls in analysis.all_contracts()}
    fired, cleaned = set(), set()
    for path in FIXTURES:
        contract, expect = _parse_header(path)
        (fired if expect == "fire" else cleaned).add(contract)
    assert fired == names, f"contracts without a firing fixture: {names - fired}"
    assert cleaned == names, f"contracts without a clean fixture: {names - cleaned}"


# --- CLI (in-process, the lint-CLI test pattern) -----------------------------


def test_cli_list_contracts_and_exit_codes(capsys):
    assert analysis_main(["--list-contracts"]) == 0
    out = capsys.readouterr().out
    for cls in analysis.all_contracts():
        assert cls.name in out
        assert cls.why
    assert analysis_main(["--list-programs"]) == 0
    out = capsys.readouterr().out
    for spec in aprog.CANONICAL_PROGRAMS:
        assert spec.label in out
    assert analysis_main(["--select", "nope"]) == 2
    assert analysis_main(["--fixture", "/nonexistent/f.py"]) == 2


def test_cli_fixture_exit_codes():
    fire = os.path.join(FIXTURE_DIR, "sliver_dus_fire.py")
    clean = os.path.join(FIXTURE_DIR, "sliver_dus_clean.py")
    assert analysis_main(["--fixture", fire, "--select", "sliver-dus"]) == 1
    assert analysis_main(["--fixture", clean, "--select", "sliver-dus"]) == 0


def test_cli_json_shape(capsys):
    fire = os.path.join(FIXTURE_DIR, "span_registry_fire.py")
    assert analysis_main(
        ["--fixture", fire, "--select", "span-registry", "--json"]
    ) == 1
    doc = json.loads(capsys.readouterr().out)
    assert set(doc) == {
        "findings",
        "count",
        "programs_checked",
        "contracts",
        "contract_seconds",
    }
    assert doc["count"] == len(doc["findings"]) == 1
    assert doc["findings"][0]["contract"] == "span-registry"
    assert sorted(c.name for c in analysis.all_contracts()) == doc["contracts"]
    # per-contract wall time rides --json: only the selected contract ran
    assert set(doc["contract_seconds"]) == {"span-registry"}
    assert doc["contract_seconds"]["span-registry"] >= 0


def test_contract_ids_are_kebab_case():
    for cls in analysis.all_contracts():
        assert re.fullmatch(r"[a-z][a-z0-9-]+", cls.name), cls.name


def test_select_unknown_contract_raises():
    art = analysis.trace_artifact(
        lambda x: x + 1.0,
        jax.ShapeDtypeStruct((4,), jnp.float32),
        label="t",
        kind="fn",
    )
    with pytest.raises(ValueError, match="unknown contract"):
        analysis.check(art, contract="nope")


# --- analyzer robustness (satellite: nested bodies, donation, opacity) -------


def test_taint_flows_through_nested_scan_and_while():
    """A source inside a scan/while body taints the wrapper eqn's outputs
    (conservative flow-through), and taint entering a nested body is not
    laundered by the wrapper."""
    from jax import lax

    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()[:8]), ("x",))
    perm = [(i, (i + 1) % 8) for i in range(8)]

    def body(x):
        def scan_body(carry, _):
            return lax.ppermute(carry, "x", perm), ()

        shifted, _ = lax.scan(scan_body, x, None, length=2)
        y = shifted * 2.0  # must be tainted: the source is INSIDE the scan

        def while_body(c):
            return c + y  # taint entering the while body

        z = lax.while_loop(lambda c: c.sum() < 0.0, while_body, x * 1.0)
        return y + z

    fn = shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                   check_vma=False)
    closed = jax.make_jaxpr(fn)(jnp.zeros((8, 16), jnp.float32))
    # inside the shard_map body: the mul consuming the scan result and the
    # while consuming y are both tainted
    (inner,) = [
        j
        for j in jx.walk(closed.jaxpr)
        if any(e.primitive.name == "scan" for e in j.eqns)
    ]
    rows = jx.taint_rows(
        inner,
        source=lambda e: e.primitive.name == "ppermute",
        watch=lambda e: e.primitive.name in ("mul", "while"),
    )
    whiles = [r for r in rows if r.primitive == "while"]
    muls = [r for r in rows if r.primitive == "mul"]
    assert whiles and all(r.tainted for r in whiles), rows
    # the mul on the scan output is tainted; the x * 1.0 seed is not —
    # flow-through is conservative, not everything-taints
    assert any(r.tainted for r in muls) and not all(r.tainted for r in muls), rows


def test_pallas_opacity_is_conservative():
    """The deliberate split (analysis/jaxpr.py vs analysis/kernels.py):
    TAINT analysis holds pallas calls opaque-conservative — taint entering
    a pallas call flows through to its consumers, because the kernel
    jaxpr's ref-mutation vars do not map back and descending would lose
    the taint and false-negative here — while the KERNEL verifier descends
    into the very same calls on purpose, through the call's own metadata
    (grid, BlockSpec index maps), where the questions are kernel-level."""
    import jax.experimental.pallas as pl
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()[:8]), ("x",))
    perm = [(i, (i + 1) % 8) for i in range(8)]

    def copy_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def pcopy(x):
        return pl.pallas_call(
            copy_kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True,
        )(x)

    def body(x):
        recv = lax.ppermute(x, "x", perm)
        laundered = pcopy(recv)  # an opaque hop over the exchanged data
        return pcopy(laundered)  # must STILL be tainted

    fn = shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                   check_vma=False)
    closed = jax.make_jaxpr(fn)(jnp.zeros((8, 16), jnp.float32))
    rows = jx.pallas_taint_rows(closed)
    assert len(rows) == 2 and all(t for _, t in rows), rows
    # ...and the kernel verifier opens the same two calls it held opaque
    from stencil_tpu.analysis import kernels as akern

    reports = akern.kernel_reports(closed)
    assert len(reports) == 2
    for rep in reports:
        assert rep.outputs and rep.outputs[0].footprint is not None
        assert not rep.parallel_dims  # undeclared grids are sequential


def test_donation_hazards_on_nested_jit():
    """The jaxpr-level donation facts: a donated-and-reused buffer is a
    hazard; donated-and-dead is not; an aliased operand with a plain later
    read is not (anti-dependency scheduling orders the reader first)."""
    scale = jax.jit(lambda x: x * 2.0, donate_argnums=0)

    def bad(x):
        return scale(x) + x

    def good(x):
        return scale(x + 1.0)

    x = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    bad_j = jax.make_jaxpr(bad)(x)
    assert any(jx.donation_hazards(j) for j in jx.walk(bad_j.jaxpr))
    good_j = jax.make_jaxpr(good)(x)
    assert not any(jx.donation_hazards(j) for j in jx.walk(good_j.jaxpr))


# --- the static VMEM prune (tune space + ladder) -----------------------------


@pytest.fixture
def tune_dir(tmp_path, monkeypatch):
    """Hermetic tuned-config cache (the exchange-routes suite's pattern) —
    searches run here must not persist winners into the session cache other
    suites' auto-mode planners consult."""
    from stencil_tpu import tune

    monkeypatch.setenv("STENCIL_TUNE_CACHE", str(tmp_path))
    monkeypatch.delenv("STENCIL_TUNE", raising=False)
    tune.reset_memo()
    yield tmp_path
    tune.reset_memo()


def _mk_dd(nq=1, exchange_route=None):
    from stencil_tpu.core.radius import Radius
    from stencil_tpu.domain import DistributedDomain

    dd = DistributedDomain(16, 16, 16)
    dd.set_radius(Radius.constant(1))
    dd.set_devices(jax.devices()[:8])
    dd.set_halo_multiplier(2)
    if exchange_route is not None:
        dd.set_exchange_route(exchange_route)
    hs = [dd.add_data(f"q{i}") for i in range(nq)]
    dd.realize()
    for i, h in enumerate(hs):
        dd.init_by_coords(
            h, lambda x, y, z, i=i: jnp.sin(0.1 * (x + y + z) + i)
        )
    return dd


def _fused_straddling_budget(dd, static_plan):
    """A scoped-VMEM budget that admits every array-halo footprint of the
    space but rejects the fused-halo twin (whose side-buffer blocks the
    stream planner's depth gate never modeled) — computed from the same
    model, so the pin cannot rot with recalibration."""
    from stencil_tpu.ops.stream_plan import plain_wavefront_plan, stream_plan_vmem_bytes

    e_static, margin = stream_plan_vmem_bytes(dd, static_plan)
    plain = plain_wavefront_plan(dd, static_plan) or static_plan
    e_fused, _ = stream_plan_vmem_bytes(dd, dict(plain, halo="fused"))
    assert e_fused > e_static
    return (e_static + e_fused) // 2 + margin


def test_pruned_candidate_never_compiles(monkeypatch, tune_dir):
    """The acceptance pin: a candidate the static verdict prunes gets ZERO
    compile attempts — the search's build_run is never invoked for it
    (previously it compiled and the Mosaic VMEM_OOM was caught at trial
    time)."""
    from stencil_tpu import tune
    from stencil_tpu.ops import stream as sm
    from stencil_tpu.tune import space as tune_space
    from stencil_tpu.tune.runners import autotune_stream

    dd = _mk_dd(exchange_route="yzpack_xla")  # the fused twin is eligible
    with tune.disabled():
        static_plan = sp.plan_stream(dd, 1, "auto", False)
    budget = _fused_straddling_budget(dd, static_plan)
    built_plans = []
    real_build = sm._build_stream_step

    def spy(dd_, kernel, x_radius, plan, interpret, donate=True):
        built_plans.append(dict(plan))
        return real_build(dd_, kernel, x_radius, plan, interpret, donate=donate)

    monkeypatch.setattr(sm, "_build_stream_step", spy)
    # control: under the calibrated default budget the twin IS a candidate
    cands, _ = tune_space.stream_space(dd, 1, False, static_plan)
    assert any(c["halo"] == "fused" for c in cands), cands
    monkeypatch.setenv("STENCIL_VMEM_LIMIT_BYTES", str(budget))
    report = autotune_stream(
        dd, aprog.mean6_kernel, interpret=True, reps=1, rt=0.0,
    )
    assert built_plans, "the surviving candidates must still compile"
    assert all(p.get("halo") != "fused" for p in built_plans), built_plans
    assert report.pruned >= 1


def test_ladder_prefilter_descends_without_building():
    """resilience/ladder.py: a rung the static prefilter rejects descends
    — recorded as a VMEM_OOM descent — with its build NEVER invoked; an
    exhausted ladder raises the reject."""
    from stencil_tpu.resilience.ladder import DegradationLadder, Rung
    from stencil_tpu.resilience.taxonomy import FailureClass

    calls = []

    def build_a():
        calls.append("a")
        return lambda *a: "a"

    def build_b():
        calls.append("b")
        return lambda *a: "b"

    a = Rung(name="deep", build=build_a, state={"fits": False})
    b = Rung(name="shallow", build=build_b, state={"fits": True})

    ladder = DegradationLadder(
        a,
        lower=lambda rung, cls, exc: b if rung is a else None,
        label="t",
        prefilter=lambda rung: None if rung.state["fits"] else "over budget",
    )
    assert ladder.step() == "b"
    assert calls == ["b"], "the rejected rung must never build"
    assert ladder.descents == [("deep", FailureClass.VMEM_OOM)]

    with pytest.raises(RuntimeError, match="statically prefiltered"):
        DegradationLadder(
            Rung(name="only", build=build_a, state={}),
            lower=lambda *a: None,
            label="t",
            prefilter=lambda rung: "over budget",
        )


def test_check_vmem_verdicts():
    """The public verdict: fits under the calibrated budget, rejects under
    a tiny one, names the plan in the reason."""
    dd = _mk_dd()
    plan = {"route": "wavefront", "m": 2, "z_slabs": False}
    assert analysis.check_vmem(dd, plan) is None
    reason = analysis.check_vmem(dd, plan, budget=1024)
    assert reason is not None and "wavefront[m=2]" in reason
    with pytest.raises(ValueError, match="not a stream plan"):
        analysis.check_vmem(dd, {"route": "warp"})


# --- the static Mosaic-legality prune (check_vmem's twin) --------------------


def test_check_kernel_legal_verdicts(monkeypatch):
    """The public legality verdict: the canonical f32 stream plans are
    legal — including under tier-1's ambient x64, where no Mosaic runs —
    but in a TPU process with x64 enabled every plan is rejected (Mosaic
    index arithmetic is 32-bit); a malformed plan raises like
    check_vmem."""
    from stencil_tpu.analysis import kernels as akern

    dd = _mk_dd()
    plan = {"route": "wavefront", "m": 2, "z_slabs": False}
    with jax.enable_x64(True):
        assert analysis.check_kernel_legal(dd, plan) is None  # CPU: no veto
        monkeypatch.setattr(akern, "_mosaic_target", lambda: True)
        reason = analysis.check_kernel_legal(dd, plan)
        assert reason is not None and "int64" in reason, reason
    monkeypatch.setattr(akern, "_mosaic_target", lambda: False)
    assert analysis.check_kernel_legal(dd, plan) is None
    with pytest.raises(ValueError, match="not a stream plan"):
        analysis.check_kernel_legal(dd, {"route": "warp"})


def test_stream_space_prunes_illegal_kernel_statically(monkeypatch, tune_dir):
    """tune/space.py consults analysis.check_kernel_legal beside
    check_vmem: in a TPU process under x64 (Mosaic-illegal index
    arithmetic for every kernel) the whole non-static space is prefiltered
    — the static plan alone survives, it being the no-tune fallback under
    defense."""
    from stencil_tpu import tune
    from stencil_tpu.analysis import kernels as akern
    from stencil_tpu.ops.stream_plan import plan_stream
    from stencil_tpu.tune import space

    dd = _mk_dd()
    with tune.disabled():
        static_plan = plan_stream(dd, 1, "auto", False)
    cands, prefiltered = space.stream_space(dd, 1, False, static_plan)
    assert len(cands) > 1, "control: the space is non-trivial on CPU"
    monkeypatch.setattr(akern, "_mosaic_target", lambda: True)
    with jax.enable_x64(True):
        cands64, prefiltered64 = space.stream_space(
            dd, 1, False, static_plan
        )
    # only the static pick survives (both its alias twins count as static
    # — alias is excluded from the static-identity comparison)
    assert len(cands64) < len(cands)
    skip = ("halo_multiplier", "alias")
    for c in cands64:
        assert all(
            c.get(k) == v for k, v in static_plan.items() if k not in skip
        ), c
    assert prefiltered64 >= prefiltered + len(cands) - len(cands64)


def test_illegal_candidate_never_compiles(monkeypatch, tune_dir):
    """The acceptance pin, check_vmem-style: a statically-illegal tuner
    candidate gets ZERO compile attempts — in a (simulated) TPU process
    under x64 the build spy sees only the static fallback plan, and the
    report counts the pruned space."""
    from stencil_tpu import tune
    from stencil_tpu.analysis import kernels as akern
    from stencil_tpu.ops import stream as sm
    from stencil_tpu.tune.runners import autotune_stream

    dd = _mk_dd()
    with tune.disabled():
        static_plan = sp.plan_stream(dd, 1, "auto", False)
    built_plans = []
    real_build = sm._build_stream_step

    def spy(dd_, kernel, x_radius, plan, interpret, donate=True):
        built_plans.append(dict(plan))
        return real_build(dd_, kernel, x_radius, plan, interpret, donate=donate)

    monkeypatch.setattr(sm, "_build_stream_step", spy)
    monkeypatch.setattr(akern, "_mosaic_target", lambda: True)
    with jax.enable_x64(True):
        report = autotune_stream(
            dd, aprog.mean6_kernel, interpret=True, reps=1, rt=0.0,
        )
    assert report.pruned >= 1
    survivors = {(p["route"], p.get("m")) for p in built_plans}
    assert survivors <= {(static_plan["route"], static_plan.get("m"))}, built_plans


def test_ladder_prefilter_tuple_descends_compile_reject():
    """resilience/ladder.py: a ``(reason, FailureClass)`` tuple verdict —
    the kernel legality model's form — descends with the NAMED class
    recorded (COMPILE_REJECT, not the VMEM_OOM default) and the rejected
    rung's build never invoked."""
    from stencil_tpu.resilience.ladder import DegradationLadder, Rung
    from stencil_tpu.resilience.taxonomy import FailureClass

    calls = []

    def build_a():
        calls.append("a")
        return lambda *a: "a"

    def build_b():
        calls.append("b")
        return lambda *a: "b"

    a = Rung(name="illegal", build=build_a, state={"legal": False})
    b = Rung(name="fallback", build=build_b, state={"legal": True})

    ladder = DegradationLadder(
        a,
        lower=lambda rung, cls, exc: b if rung is a else None,
        label="t",
        prefilter=lambda rung: None
        if rung.state["legal"]
        else ("unsupported unaligned shape", FailureClass.COMPILE_REJECT),
    )
    assert ladder.step() == "b"
    assert calls == ["b"], "the rejected rung must never build"
    assert ladder.descents == [("illegal", FailureClass.COMPILE_REJECT)]


def test_kernel_ledger_matches_tree():
    """The jax-free PALLAS_KERNELS ledger (analysis/registry.py) pins the
    real tree in BOTH directions: every top-level ops/ function issuing a
    pallas_call is ledgered, and no ledger entry names a kernel that no
    longer exists (allowlists must not rot)."""
    import ast

    from stencil_tpu.lint.rules.kernel_ledger import _issues_pallas_call

    repo = os.path.dirname(HERE)
    found = {}
    ops_dir = os.path.join(repo, "stencil_tpu", "ops")
    for fname in sorted(os.listdir(ops_dir)):
        if not fname.endswith(".py"):
            continue
        rel = f"stencil_tpu/ops/{fname}"
        with open(os.path.join(ops_dir, fname)) as fh:
            tree = ast.parse(fh.read())
        names = tuple(
            node.name
            for node in tree.body
            if isinstance(node, ast.FunctionDef) and _issues_pallas_call(node)
        )
        if names:
            found[rel] = names
    assert found == dict(aregistry.PALLAS_KERNELS)


def _inplace_cases():
    return [
        (kernel, fixture)
        for kernel, fixtures in sorted(aregistry.INPLACE_PASSES.items())
        for fixture in fixtures
    ]


@pytest.mark.parametrize("kernel,fixture", _inplace_cases())
def test_inplace_passes_are_proven_aliased(kernel, fixture):
    """Every pass the registry names as running in place (``INPLACE_PASSES``)
    has its fixtures: each traces the REAL kernel with every streamed output
    aliased onto its input — so ``inplace-order`` has pairs to judge — and
    comes out clean.  A fixture that lost its alias would pass vacuously."""
    from stencil_tpu.analysis import kernels
    from stencil_tpu.telemetry import names as tm

    assert kernel in tm.ALL_KERNELS
    art = _load(os.path.join(FIXTURE_DIR, fixture))
    reps = [r for r in kernels.kernel_reports(art.closed) if r.label == kernel]
    assert reps, f"{fixture} traces no {kernel}"
    for rep in reps:
        assert rep.aliases and not rep.parallel_dims, rep
        assert all(a.footprint for a in rep.aliases.values()), rep.notes
    assert not kernels.check_inplace_order(art)


def test_inplace_order_reads_fetch_and_flush_steps():
    """The contract's model on the fire fixture: the hazard it names is the
    first flushed block that a LATER step fetches (plane 0 is read before it
    is flushed; plane 1, flushed after step 1, is fetched at step 2)."""
    from stencil_tpu.analysis import kernels

    (msg,) = kernels.check_inplace_order(
        _load(os.path.join(FIXTURE_DIR, "inplace_order_fire.py"))
    )
    assert "flushes block (1, 0, 0) after grid step 1" in msg and "step 2" in msg


def test_inplace_order_judges_an_output_aliased_onto_another_quantity():
    """A rename (ISSUE 36) lands an output on ANOTHER quantity's operand: the
    contract takes the pair as the call carries it.  The real renaming pass
    aliases its one output onto operand 2 (raw ``u_prev``; 0 is ``origin``,
    1 raw ``u``) and is clean; the synthetic one whose target is fetched two
    planes behind an un-lagged write fires on that operand."""
    from stencil_tpu.analysis import kernels

    art = _load(os.path.join(FIXTURE_DIR, "inplace_order_plane_renamed_clean.py"))
    (rep,) = kernels.kernel_reports(art.closed)
    assert {o: a.index for o, a in rep.aliases.items()} == {0: 2} and len(rep.outputs) == 1
    assert not kernels.check_inplace_order(art)
    (msg,) = kernels.check_inplace_order(
        _load(os.path.join(FIXTURE_DIR, "inplace_order_renamed_fire.py"))
    )
    assert "output 0 aliases in[1]" in msg
    assert "flushes block (1, 0, 0) after grid step 1" in msg and "step 3" in msg


def test_a_boundary_block_counts_the_arrays_extent():
    """A block wider than the array in the minor dim (ISSUE 41), whole lane
    tiles: ONE block covers the dim -- its cells are the array's 200, not its
    own 256 -- so the clean fixture, in place and a plane behind its reads, is
    in order and covered, and every kernel contract is quiet; the same windows
    at 250 lanes fire ``tiling-legal`` alone, naming the boundary block."""
    from stencil_tpu.analysis import kernels

    art = _load(os.path.join(FIXTURE_DIR, "tiling_legal_boundary_clean.py"))
    (rep,) = kernels.kernel_reports(art.closed)
    (use,) = rep.inputs
    assert (use.block_shape, use.array_shape, use.nblocks) == ((1, 16, 256), (4, 16, 200), (4, 1, 1))
    assert kernels._block_box(use, (2, 0, 0)) == ((2, 3), (0, 16), (0, 200))
    assert rep.aliases
    shape_contracts = ("kernel-coverage", "inplace-order", "kernel-race", "tiling-legal")
    for contract in shape_contracts:
        assert not analysis.check(art, contract=contract), contract
    fire = _load(os.path.join(FIXTURE_DIR, "tiling_legal_boundary_fire.py"))
    findings = [f for c in shape_contracts for f in analysis.check(fire, contract=c)]
    assert len(findings) == 2  # the operand's window and the result's
    for finding in findings:
        assert finding.contract == "tiling-legal"
        assert "boundary block of extent 250" in finding.render() and "200 cells" in finding.render()


@pytest.mark.parametrize("fixture,aliased", [
    ("tiling_legal_wrap_edges_clean.py", False), ("kernel_coverage_wrap_edges_clean.py", True),
], ids=["raw-in", "raw-out"])
def test_the_wrap_pass_edge_forms_are_quiet_on_every_kernel_contract(fixture, aliased):
    """``stream_wrap_pass``'s edge forms (ISSUE 52) move a raw ``(10, 10, 130)``
    plane as ONE ``(1, 16, 256)`` boundary block, wider than the array in both
    minor dims: counted on the array's extent, legal on the granule; the
    ``raw_out`` form's x halo planes are covered by its alias alone, onto an
    operand no block map reads."""
    from stencil_tpu.analysis import kernels

    art = _load(os.path.join(FIXTURE_DIR, fixture))
    (rep,) = kernels.kernel_reports(art.closed)
    raw = (10, 10, 130)  # (the aliased operand, in ``pl.ANY``, reads as one block of the whole array)
    edge = [u for u in list(rep.inputs) + list(rep.outputs) if u.array_shape == raw != u.block_shape]
    assert len(edge) == 1 and all((u.block_shape, u.nblocks) == ((1, 16, 256), (10, 1, 1)) for u in edge)
    assert bool(rep.aliases) == aliased
    for contract in ("kernel-coverage", "inplace-order", "kernel-race", "tiling-legal"):
        assert not analysis.check(art, contract=contract), contract


@pytest.mark.parametrize("z,want", [(10, None), (122, None)], ids=["boundary-16-in-128", "whole-tiles-128"])
def test_check_kernel_legal_takes_the_z_slab_boundary_block(z, want):
    """The plan surface models the z-slab wavefront's window as the pass
    builds it -- ``lane_pad_width(Zr)`` lanes over the raw block's ``Zr`` --
    and finds it legal; a window that were neither the array's extent nor
    whole lane tiles would not be."""
    from stencil_tpu.analysis import kernels
    from stencil_tpu.core.radius import Radius
    from stencil_tpu.domain import DistributedDomain

    dd = DistributedDomain(16, 16, z)
    dd.set_radius(Radius.constant(3))
    dd.set_devices(jax.devices()[:4])
    dd.set_partition(2, 2, 1)
    dd.add_data("q")
    dd.realize(allocate=False)
    plan = {"route": "wavefront", "m": 3, "z_slabs": True, "grouping": "joint"}
    assert kernels.check_kernel_legal(dd, plan) is want
    assert kernels._off_granule_boundary(250, 200, 128) and not kernels._off_granule_boundary(256, 200, 128)
    assert not kernels._off_granule_boundary(200, 200, 128)


@pytest.mark.parametrize("build,wires", [
    (pfp._astaroth, ()), (pfp._jacobi_zring, ("x", "y"))], ids=["astaroth-1x1x1", "jacobi-zring-2x2x1"])
def test_the_z_slab_extension_sends_nothing_to_itself(build, wires):
    """The z sweep of the two z-slab cells' programs, traced with the blend
    kernels on as the chip has them (ISSUE 56): under an ``exchange.z`` scope
    the one-device program holds no ``ppermute`` and no ``.at[].set`` (a
    ``scatter`` here, a whole-half ``dynamic-update-slice`` on the chip) -- its
    slab extension is two self-wrap kernels a quantity a macro, under the
    ``exchange.<axis>.wrap`` scopes --, and the mesh-[2,2,1] one holds them for
    the axes the mesh splits alone, one landing a received piece: nothing is
    sent over z, to oneself."""
    with aprog.tpu_shaped_trace():
        closed = build()
    under_z = [e for e in jx.iter_eqns(closed) if "exchange.z" in jx.name_stack_str(e).split("/")]
    sent = [e.params["axis_name"] for e in under_z if e.primitive.name == "ppermute"]
    landed = [e for e in under_z if e.primitive.name in ("scatter", "dynamic_update_slice")]
    wraps = [e for e in under_z if e.primitive.name == "pallas_call"]
    assert {a for axes in sent for a in axes} == set(wires), sent
    assert len(landed) == len(sent)
    if wires:
        assert not wraps and all(sent.count((a,)) == len(sent) // len(wires) for a in wires)
    else:
        scopes = [s for e in wraps for s in jx.name_stack_str(e).split("/") if s.endswith(".wrap")]
        assert wraps and scopes.count("exchange.y.wrap") == scopes.count("exchange.x.wrap") == len(wraps) // 2


# --- tier-2: the real CLI end to end -----------------------------------------


@pytest.mark.slow
def test_cli_subprocess_whole_matrix(tmp_path):
    """``python -m stencil_tpu.analysis`` exits 0 on the shipped tree (the
    acceptance command, run exactly as CI/check_all.sh invokes it)."""
    import subprocess
    import sys

    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["STENCIL_TUNE_CACHE"] = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "stencil_tpu.analysis", "--json"],
        capture_output=True,
        text=True,
        timeout=600,
        env=env,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["count"] == 0
    assert doc["programs_checked"] == len(aprog.CANONICAL_PROGRAMS)

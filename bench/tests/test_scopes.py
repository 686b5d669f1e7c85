"""The window program's named scopes and the program's host spans, and the
reduction that reads them back (``bench/scopes.py``): which ops carry a
scope in every schedule and exchange, self time on nested events, and the
attribution of a real traced run, on the CPU and on a recorded TPU trace."""

from __future__ import annotations

import glob
import gzip
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from conftest import ROOT

HERE = Path(__file__).resolve().parent
# The benchmark cell's engine, at the quickstart's size (4 x 256, K = 32 +
# 32); the recorded TPU trace is a run of the same.
QUICKSTART = dict(n_areas=4, n_per_area=256, k_intra=32, k_inter=32)
CELL_ENGINE = dict(neuron_model="lif", schedule="structure_aware",
                   delivery_backend="event", s_max_floor=1024)
WINDOW_SPANS = ("repro.window.dispatch", "repro.window.wait",
                "repro.window.spike_count")
BUILD_SPANS = ("repro.build.draw", "repro.build.invert")
# Ops that only move the program along: loop and call plumbing, and the
# copies XLA inserts for loop-carried buffers.
_PLUMBING = {"parameter", "constant", "get-tuple-element", "tuple",
             "bitcast", "copy", "while", "conditional", "call",
             "opt-barrier", "after-all", "partition-id", "replica-id"}
_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.-]+)\s.*\{\s*$")
_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*(.*?)\s+([\w-]+)\(")


def unscoped_ops(hlo: str) -> list[tuple[str, str, str]]:
    """``(instruction, opcode, shape)`` of each op the compiled program
    runs (the entry, loop bodies and conditions, conditional branches)
    that has no scope, leaving out plumbing, scalars and constants (ops
    whose operands are all constants)."""
    from bench import scopes

    _, names, _ = scopes.hlo_op_names(hlo)
    run = set(re.findall(r"(?:body|condition)=%?([\w.-]+)", hlo))
    for m in re.finditer(r"branch_computations=\{([^}]*)\}", hlo):
        run |= {x.strip().lstrip("%") for x in m.group(1).split(",")}
    consts = set(re.findall(r"%([\w.-]+) = \S+ constant\(", hlo))
    comp, out = None, []
    for line in hlo.splitlines():
        m = _HEADER.match(line)
        if m and "=" not in line.split("{")[0].split("(")[0]:
            comp = "ENTRY" if m.group(1) else m.group(2)
            continue
        m = _LINE.match(line)
        if not m or (comp != "ENTRY" and comp not in run):
            continue
        instr, shape, opcode = m.groups()
        args = re.findall(r"(?<![\w=])%([\w.-]+)", line.split("=", 1)[1])
        if (opcode in _PLUMBING or re.fullmatch(r"\w+\[\]", shape)
                or set(args) <= consts):
            continue
        if scopes.scope_of(names.get(instr)) == scopes.OTHER:
            out.append((instr, opcode, shape.split("{")[0]))
    return out


def assert_scoped(hlo: str, d: int, a: int, n: int, *, every=True):
    """Each scope name is in the program, and the only unscoped op is the
    assembly of the window's spike raster ``pred[D, A, n]``, which
    ``lax.scan`` (or the unrolled window's stack) emits outside the
    cycle's body."""
    from bench import scopes

    if every:
        missing = [s for s in scopes.SCOPES if s not in hlo]
        assert not missing, missing
    raster = {f"pred[{d},{a},{n}]"}
    left = [op for op in unscoped_ops(hlo) if op[2] not in raster]
    assert not left, left


def test_program_scopes_are_the_benchmark_scopes():
    from bench import scopes
    from repro.core import schedule

    assert schedule.SCOPES == scopes.SCOPES


LOCAL_VARIANTS = {
    "conventional": dict(schedule="conventional"),
    "superstep": dict(schedule="structure_aware"),
    "legacy": dict(schedule="structure_aware", superstep=False),
    "unrolled": dict(schedule="structure_aware", superstep_unroll=True),
    "overlapped": dict(schedule="structure_aware", overlap_exchange=True),
    "adaptive": dict(schedule="structure_aware", adaptive_exchange=True),
    "adaptive_conventional": dict(schedule="conventional",
                                  adaptive_exchange=True),
}


@pytest.mark.parametrize("backend", ["event", "scatter"])
@pytest.mark.parametrize("variant", sorted(LOCAL_VARIANTS))
def test_local_window_ops_carry_a_scope(variant, backend):
    from repro.core import (EngineConfig, build_network, make_simulation,
                            mam_benchmark_spec)

    spec = mam_benchmark_spec(n_areas=4, n_per_area=64, k_intra=8,
                              k_inter=8)
    net = build_network(spec, seed=12, outgoing=True)
    cfg = EngineConfig(neuron_model="lif", delivery_backend=backend,
                       s_max_floor=16, **LOCAL_VARIANTS[variant])
    eng = make_simulation(spec, cfg, net=net)
    st = eng.init()
    shape = (net.delay_ratio, 4, net.n_pad)
    if eng.window_overlap is None:
        assert_scoped(eng.window.lower(st).compile().as_text(), *shape)
        return
    inflight = eng.init_inflight()
    assert_scoped(eng.window_overlap.lower(st, inflight).compile().as_text(),
                  *shape)
    # The drain is the window-end receive alone.
    drain = eng.drain.lower(st, inflight).compile().as_text()
    assert_scoped(drain, *shape, every=False)
    assert "inter_exchange" in drain


@pytest.mark.parametrize("exchange", ["dense", "routed"])
def test_mesh_window_ops_carry_a_scope(exchange, tmp_path):
    """The distributed windows on 8 forced host devices (a 4 x 2 mesh, and
    the conventional schedule's round-robin over all 8)."""
    code = textwrap.dedent(f"""
        import jax
        from repro.core import EngineConfig, build_network, mam_benchmark_spec
        from repro.core.factory import make_simulation

        spec = mam_benchmark_spec(n_areas=8, n_per_area=32, k_intra=4,
                                  k_inter=4)
        net = build_network(spec, seed=12, size_multiple=8, outgoing=True)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        variants = {{
            "superstep": dict(schedule="structure_aware"),
            "legacy": dict(schedule="structure_aware", superstep=False),
            "overlapped": dict(schedule="structure_aware",
                               overlap_exchange=True),
            "adaptive": dict(schedule="structure_aware",
                             adaptive_exchange=True),
            "conventional": dict(schedule="conventional"),
        }}
        for name, kw in variants.items():
            for backend in ("event", "scatter"):
                exch = "dense" if kw["schedule"] == "conventional" else \\
                    {exchange!r}
                cfg = EngineConfig(neuron_model="lif",
                                   delivery_backend=backend, exchange=exch,
                                   s_max_floor=16, **kw)
                eng = make_simulation(spec, cfg, net=net, mesh=mesh)
                st = eng.init()
                if eng.window_overlap is None:
                    low = eng.window.lower(st)
                else:
                    low = eng.window_overlap.lower(st, eng.init_inflight())
                path = {str(tmp_path)!r} + f"/{{name}}_{{backend}}.hlo"
                open(path, "w").write(low.compile().as_text())
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=540)
    assert out.returncode == 0, out.stderr[-4000:]
    paths = sorted(tmp_path.glob("*.hlo"))
    assert len(paths) == 10
    for path in paths:
        hlo = path.read_text()
        conventional = path.name.startswith("conventional")
        # Per device: the raster of its areas x its neuron window.
        a, n = (8, 32 // 8) if conventional else (8 // 4, 32 // 2)
        try:
            assert_scoped(hlo, 10, a, n)
        except AssertionError as e:
            raise AssertionError(f"{path.name}: {e}") from None


# --- the reduction ---------------------------------------------------------

def _synthetic():
    """One device line: a loop (0-10 ms) holding two ops and a gap, a
    scatter nested in the second; then an op of another executable."""
    ms = 1e6
    op = "/device:TPU:0"
    return {
        "ops": [
            (op, "while.1", 0.0, 10 * ms, {}),
            (op, "fusion.2", 1 * ms, 3 * ms, {}),
            (op, "fusion.3", 5 * ms, 4 * ms, {}),
            (op, "scatter.4", 6 * ms, 2 * ms, {}),
            (op, "reduce.5", 12 * ms, 1 * ms, {}),
        ],
        "modules": [(op, "jit_window(7)", 0.0, 11 * ms),
                    (op, "jit__reduce_sum(8)", 12 * ms, 1 * ms)],
        "spans": [("repro.window.dispatch", 0.0, 1 * ms)],
    }


_SYNTHETIC_HLO = """HloModule jit_window, entry_computation_layout={()->f32[]}

%fused_computation.2 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(window)/while/body/neuron_update/mul"}
}

ENTRY %main (x: f32[8]) -> f32[] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %while.1 = f32[8]{0} while(%x), condition=%c, body=%b, metadata={op_name="jit(window)/while"}
  %fusion.2 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.2
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%missing
  %scatter.4 = f32[8]{0} scatter(%fusion.3), metadata={op_name="jit(window)/while/body/intra_deliver/vmap(ring)/scatter-add"}
  ROOT %reduce.5 = f32[] reduce(%x), metadata={op_name="jit(window)/inter_exchange/reduce_sum"}
}
"""


def test_self_time_subtracts_nested_events():
    from bench import scopes

    ev = _synthetic()
    selfs = scopes.self_times(ev["ops"])
    ms = 1e6
    # while: 10 - (3 + 4); fusion.3: 4 - 2 (the scatter in it).
    assert selfs == pytest.approx([3 * ms, 3 * ms, 2 * ms, 2 * ms, 1 * ms])
    # Clipped to [2, 7) ms: while 5 - (2 + 2), fusion.3 2 - 1.
    clipped = scopes.self_times(ev["ops"], (2 * ms, 7 * ms))
    assert clipped == pytest.approx([1 * ms, 2 * ms, 1 * ms, 1 * ms, 0.0])


def test_scope_seconds_attributes_by_innermost_scope_within_busy_time():
    from bench import scopes

    out = scopes.scope_seconds(_synthetic(), _SYNTHETIC_HLO)
    # fusion.2 takes its callee's op_name; fusion.3 its operand's; the
    # innermost scope of the scatter is ``ring``; reduce.5 ran in another
    # executable, so its op_name in this HLO does not apply.
    assert out["neuron_update"] == pytest.approx(3e-3 + 2e-3)
    assert out["ring"] == pytest.approx(2e-3)
    assert out["intra_deliver"] == 0.0
    assert out["inter_exchange"] == 0.0
    assert out["other"] == pytest.approx(3e-3 + 1e-3)
    assert out["module_s"] == pytest.approx(10e-3)
    assert out["busy_s"] == pytest.approx(11e-3)
    scoped = sum(out[s] for s in scopes.SCOPES)
    assert scoped + out["other"] <= out["busy_s"] + 1e-12
    assert out["n_devices"] == 1


_LOOP_HLO = """HloModule jit_window

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]) parameter(0)
  %g = f32[8]{0} get-tuple-element(%p), index=1
  %d = f32[8]{0} dynamic-update-slice(%g, %g)
  ROOT %t = (s32[], f32[8]) tuple(%g, %d)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="state.ring"}
  %gather.1 = f32[8]{0} gather(%x), metadata={op_name="jit(window)/intra_deliver/gather"}
  %tuple.2 = (s32[], f32[8]) tuple(%x, %gather.1)
  %while.3 = (s32[], f32[8]) while(%tuple.2), condition=%cond, body=%body
  ROOT %copy.4 = f32[8]{0} copy(%x)
}
"""


def test_hlo_op_names_follow_operands_and_loops_xla_made():
    """XLA's own loop (no metadata) takes its operand's scope, and the ops
    of its body take the loop's; a copy of an argument stays unplaced."""
    from bench import scopes

    module, names, sigs = scopes.hlo_op_names(_LOOP_HLO)
    assert module == "jit_window"
    for instr in ("tuple.2", "while.3", "p", "g", "d", "t"):
        assert scopes.scope_of(names.get(instr)) == "intra_deliver", instr
    assert "x" not in names and "copy.4" not in names
    assert sigs["gather.1"] == ("f32[8]{0}", "gather")


@pytest.mark.parametrize("op_name, scope", [
    ("jit(window)/while/body/neuron_update/add", "neuron_update"),
    ("jit(window)/intra_deliver/inter_exchange/scatter-add", "inter_exchange"),
    ("jit(window)/shard_map/vmap(intra_deliver)/gather", "intra_deliver"),
    ("jit(window)/ringside/add", "other"),
    ("jit(window)/while", "other"),
    (None, "other"),
])
def test_scope_of_takes_the_innermost_name(op_name, scope):
    from bench import scopes

    assert scopes.scope_of(op_name) == scope


def test_span_seconds_sums_the_spans_of_a_name():
    from bench import scopes

    ev = {"spans": [("repro.build.draw", 0.0, 2e9),
                    ("repro.build.draw", 5e9, 1e9),
                    ("repro.build.invert", 2e9, 3e9)]}
    assert scopes.span_seconds(ev, "repro.build.draw") == pytest.approx(3.0)
    assert scopes.span_seconds(ev, "repro.build.upload") is None


def _quickstart_trace(trace_dir):
    """A traced quickstart-size run: the build, then four windows."""
    import jax
    from repro.core import (EngineConfig, build_network, make_simulation,
                            mam_benchmark_spec)
    from repro.core.schedule import run_windows

    spec = mam_benchmark_spec(**QUICKSTART)
    cfg = EngineConfig(**CELL_ENGINE)
    net = build_network(spec, seed=12, outgoing=True)
    eng = make_simulation(spec, cfg, net=net)
    state = run_windows(eng, eng.init(), 2).state
    jax.profiler.start_trace(str(trace_dir))
    build_network(spec, seed=12, outgoing=True)
    run_windows(eng, state, 4)
    jax.profiler.stop_trace()
    return eng.window.lower(state).compile().as_text()


def test_cpu_trace_of_a_quickstart_run_is_attributed_to_scopes(tmp_path):
    from bench import scopes

    hlo = _quickstart_trace(tmp_path)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    ev = scopes.load(path[0])
    for name in WINDOW_SPANS + BUILD_SPANS:
        assert scopes.span_seconds(ev, name), name
    out = scopes.scope_seconds(ev, hlo)
    scoped = sum(out[s] for s in scopes.SCOPES)
    assert out["module_s"] > 0
    assert scoped >= 0.9 * out["module_s"], out
    assert all(out[s] > 0 for s in scopes.SCOPES), out
    assert scoped + out["other"] <= out["busy_s"] * 1.000001


def test_recorded_tpu_trace_is_attributed_to_scopes():
    """A quickstart-size run recorded on one TPU v5e (``tpu_trace/``: the
    build and four windows, as ``_quickstart_trace`` runs them, with the
    window's compiled HLO). The TPU's op events carry no ``op_name``: each
    is joined to its instruction in the HLO, shape and opcode checked."""
    from bench import scopes

    rec = HERE / "tpu_trace"
    ev = scopes.load(str(rec / "quickstart.xplane.pb.gz"))
    hlo = gzip.decompress((rec / "window_hlo.txt.gz").read_bytes()).decode()
    assert ev["ops"] and {op[0] for op in ev["ops"]} == {"/device:TPU:0"}
    for name in WINDOW_SPANS + BUILD_SPANS:
        assert scopes.span_seconds(ev, name), name
    out = scopes.scope_seconds(ev, hlo)
    scoped = sum(out[s] for s in scopes.SCOPES)
    assert out["unjoined_s"] == 0.0
    assert all(out[s] > 0 for s in scopes.SCOPES), out
    assert scoped >= 0.97 * out["module_s"], out
    assert scoped + out["other"] <= out["busy_s"] * 1.000001
    assert max(scopes.SCOPES, key=out.get) == "intra_deliver"
    # Joined to another executable's HLO, the ops are not placed.
    other = scopes.scope_seconds(ev, hlo.replace("fusion(", "fusion-x("))
    assert other["unjoined_s"] > 0.5 * out["module_s"]


def test_simulate_profile_writes_a_trace_of_the_real_run(tmp_path):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.simulate", "--model",
         "mam_benchmark", "--areas", "4", "--n-per-area", "64", "--k", "8",
         "--t-ms", "20", "--backend", "event", "--profile"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "profiler trace ->" in out.stdout
    assert "wire volume" in out.stdout
    from bench import scopes

    paths = glob.glob(str(tmp_path / "simulate_profile" / "**" /
                          "*.xplane.pb"), recursive=True)
    assert len(paths) == 1
    ev = scopes.load(paths[0])
    for name in WINDOW_SPANS:
        assert scopes.span_seconds(ev, name), name
    assert any(op[4].get("hlo_module") == "jit_window" for op in ev["ops"])

"""Scale-envelope abstract interpreter suite (tpu_swirld.analysis.flow).

Four layers, mirroring how the audit earns trust:

- **soundness**: the lattice-soundness property — for every stage a real
  small run of each engine dispatches, replay the observed call through
  the interpreter at concrete-argument intervals and assert the abstract
  output intervals contain every concrete output value (the defining
  property of the abstraction; a transfer function that under-
  approximates fails here before it can hide a real overflow);
- **teeth**: both seeded mutations (an int16-narrowed tally accumulator,
  a dropped index clip) must be *caught*, with the exact rule, file,
  line, and primitive pinpointed — a silently weakened transfer fails;
- **coverage**: every registered transfer function is exercised by the
  catalog plus a micro-trace battery (version-alias groups count as one
  transfer), and every stage name the engines dispatch at runtime maps
  to an audited spec;
- **the gates**: the shipped tree is proven clean at baseline *and* the
  1M-event envelope, suppressions demand justification text, the CLI
  exit codes hold (0 clean / 1 findings / 2 unknown primitive), and the
  bench stamp + bench_compare gate refuse dirty or missing proofs.
"""

import dataclasses
import functools
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from tpu_swirld.analysis.flow import stages
from tpu_swirld.analysis.flow.audit import (
    MUTATIONS,
    _apply_suppressions,
    main as audit_main,
    scale_audit,
    scale_audit_stamp,
)
from tpu_swirld.analysis.flow.envelope import (
    INT32_MAX,
    get_envelope,
    host_envelope_findings,
    preset_names,
)
from tpu_swirld.analysis.flow.interpret import RULE_NAMES, interpret_jaxpr
from tpu_swirld.analysis.flow.lattice import AbsVal, Interval
from tpu_swirld.analysis.flow.transfer import (
    TRANSFERS,
    UnknownPrimitiveError,
    registered_primitives,
)
from tpu_swirld.analysis.lint import Finding

pytestmark = pytest.mark.audit

_ROOT = os.path.join(os.path.dirname(__file__), "..")


@functools.lru_cache(maxsize=None)
def _audit(envelope, mutate=None):
    """One shared audit run per (envelope, mutation) for the module."""
    return scale_audit(envelope, check_coverage=False, mutate=mutate)


@pytest.fixture(scope="module", autouse=True)
def _compile_cache_hygiene():
    # this module traces the full catalog at two envelopes and replays
    # every engine's stages; drop the accumulated executables afterwards
    # so the rest of the suite runs at its usual jit-cache footprint
    yield
    _audit.cache_clear()
    jax.clear_caches()


# ----------------------------------------------------- lattice basics


def test_interval_lattice_ops():
    a, b = Interval(0, 10), Interval(5, 20)
    assert a.join(b) == Interval(0, 20)
    assert a.meet(b) == Interval(5, 10)
    assert Interval(0, 20).covers(a) and not a.covers(b)
    assert Interval(3, 3).is_point


def test_absval_literal_dtype():
    # a Python-int literal must take the jaxpr aval's dtype, not the
    # host default (int64 literals joined against int32 carries was a
    # real analyzer bug at the 1m envelope)
    v = AbsVal.from_literal(np.int32(7))
    assert v.dtype == np.dtype(np.int32) and v.iv == Interval(7, 7)


# ----------------------------------------------------- interpreter regressions


def _interp(fn, structs, ivs):
    closed = jax.make_jaxpr(fn)(*structs)
    findings = []
    res = interpret_jaxpr(closed, ivs, sentinels=(INT32_MAX,),
                          findings=findings)
    return res, findings


def test_negative_index_normalization_not_widened():
    # jnp's negative-index normalization (where(i < 0, i + n, i)) must
    # fold to the in-range branch when the operand interval decides the
    # comparison — joining both arms was the analyzer's biggest source
    # of false SW009s
    def f(x, i):
        return x[jnp.where(i < 0, i + x.shape[0], i)]

    res, findings = _interp(
        f,
        [jax.ShapeDtypeStruct((16,), np.int32),
         jax.ShapeDtypeStruct((), np.int32)],
        [(0, 99), (0, 15)],
    )
    assert not findings
    assert res.outs[0].iv == Interval(0, 99)


def test_roll_remainder_start_proven_in_bounds():
    # jnp.roll lowers to concatenate + dynamic_slice with a floored-mod
    # start; the remainder summary must keep the start inside [0, n]
    def f(x, s):
        return jnp.roll(x, -s)

    res, findings = _interp(
        f,
        [jax.ShapeDtypeStruct((16,), np.int32),
         jax.ShapeDtypeStruct((), np.int32)],
        [(0, 99), (0, 7)],
    )
    assert not findings
    assert res.outs[0].iv == Interval(0, 99)


def test_unknown_primitive_hard_fails():
    # no silent assume-top: an unmodeled primitive refuses, loudly
    def f(x):
        return lax.sin(x)

    closed = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4,), np.float32))
    with pytest.raises(UnknownPrimitiveError) as ei:
        interpret_jaxpr(closed, [None])
    assert ei.value.primitive == "sin"


# ----------------------------------------------------- soundness property

_SOUNDNESS_SEEDS = {"batch": (3,), "incremental": (3,),
                    "streaming": (3,), "mesh": (3,)}


def _soundness_violations(engine, seed):
    """Replay every stage call a real run dispatches through the
    interpreter; return containment violations (must be empty)."""
    calls, seen = [], set()

    def collect(name, fn, args, kw):
        if name in seen:
            return
        seen.add(name)
        # snapshot before dispatch: several stages donate their inputs
        calls.append((name, fn, tuple(np.asarray(a) for a in args),
                      dict(kw)))

    stages.observed_stage_names(engine, seed=seed, collect=collect)
    assert calls, f"engine {engine!r} dispatched no stages"

    bad = []
    for name, fn, args, kw in calls:
        closed, ivs = stages.trace_concrete_call(fn, args, kw)
        res = interpret_jaxpr(closed, ivs, stage=name,
                              sentinels=(INT32_MAX,))
        leaves = jax.tree_util.tree_leaves(fn(*args, **kw))
        assert len(leaves) == len(res.outs), name
        for j, (av, leaf) in enumerate(zip(res.outs, leaves)):
            arr = np.asarray(leaf)
            if arr.size == 0:
                continue
            lo, hi = float(arr.min()), float(arr.max())
            if np.isnan(lo) or np.isnan(hi):
                continue
            if not (float(av.iv.lo) <= lo and hi <= float(av.iv.hi)):
                bad.append(f"{name} out[{j}]: abstract {av.iv} misses "
                           f"concrete [{lo}, {hi}] ({arr.dtype})")
    return bad


@pytest.mark.parametrize("engine", stages.ENGINES)
def test_lattice_soundness(engine):
    for seed in _SOUNDNESS_SEEDS[engine]:
        bad = _soundness_violations(engine, seed)
        assert not bad, "\n".join(bad)


@pytest.mark.slow
@pytest.mark.parametrize("engine", stages.ENGINES)
def test_lattice_soundness_seed_sweep(engine):
    for seed in (5, 11, 23):
        bad = _soundness_violations(engine, seed)
        assert not bad, "\n".join(bad)


# ----------------------------------------------------- the shipped tree


def test_baseline_proven_clean():
    rep = _audit("baseline")
    assert rep.exit_code == 0 and rep.clean
    assert not rep.findings and not rep.unjustified and not rep.errors
    # the pipeline's intentional sentinel masking rides on justified
    # suppressions — each must carry its why-safe text
    assert rep.suppressed
    for f, note in rep.suppressed:
        assert note.strip(), f.render()
    assert len(rep.specs) == len(stages.CATALOG)


def test_envelope_1m_proven_clean():
    # the headline guarantee: the full catalog at 2**20 events /
    # 256 members, all engines, exits 0
    rep = _audit("1m")
    assert rep.exit_code == 0 and rep.clean, rep.render()


def test_stage_coverage_no_gaps():
    cmap = stages.coverage_map()
    for engine in stages.ENGINES:
        observed = stages.observed_stage_names(engine)
        assert observed, engine
        gaps = [s for s in observed if s not in cmap]
        assert not gaps, f"{engine}: uncovered stages {gaps}"


# ----------------------------------------------------- transfer coverage

#: micro-traces for primitives the consensus stages don't emit; each
#: probe must exercise its named transfer
_BATTERY = [
    ("abs", lambda x: jnp.abs(x), (-5, 5)),
    ("argmin", lambda x: jnp.argmin(x), (0, 7)),
    ("clamp", lambda x: lax.clamp(jnp.int32(0), x, jnp.int32(5)), (-9, 9)),
    ("copy", lambda x: jnp.copy(x), (0, 7)),
    ("cumsum", lambda x: jnp.cumsum(x), (0, 7)),
    ("integer_pow", lambda x: x ** 2, (0, 7)),
    ("le", lambda x: (x <= 3).astype(np.int32), (0, 7)),
    ("pad", lambda x: jnp.pad(x, (1, 1)), (0, 7)),
    ("reduce_min", lambda x: jnp.min(x), (0, 7)),
    ("rev", lambda x: jnp.flip(x), (0, 7)),
    ("xor", lambda x: x ^ 3, (0, 7)),
]


def _battery_exercised():
    ex = set()
    for name, fn, iv in _BATTERY:
        closed = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((8,), np.int32))
        got = set()
        interpret_jaxpr(closed, [iv], exercised=got)
        assert name in got, f"battery probe {name!r} exercised {sorted(got)}"
        ex |= got
    return ex


def test_transfer_registry_fully_exercised():
    # acceptance: every registered transfer is exercised by tests.
    # Aliases (e.g. "psum"/"psum_invariant") share one transfer function,
    # so coverage is counted per transfer *function*, not per name.
    exercised = set(_audit("baseline").exercised)
    exercised |= _audit("1m").exercised
    for m in sorted(MUTATIONS):
        exercised |= _audit("baseline", m).exercised
    exercised |= _battery_exercised()

    groups = {}
    for name, fn in TRANSFERS.items():
        groups.setdefault(id(fn), []).append(name)
    missed = [sorted(names) for names in groups.values()
              if not exercised & set(names)]
    assert not missed, f"transfers never exercised: {missed}"
    # the higher-order forms are interpreted structurally, not via the
    # registry — they must be exercised too
    assert {"jit", "scan", "while", "cond",
            "shard_map"} <= exercised


def test_registered_primitives_listing():
    names = registered_primitives()
    assert names == sorted(names) and len(names) == len(set(names))
    assert {"gather", "scatter", "dynamic_slice", "add", "mul",
            "convert_element_type"} <= set(names)


# ----------------------------------------------------- mutation teeth


def test_mutation_ssm_int16_accumulator_caught():
    rep = _audit("baseline", "ssm-acc-int16")
    assert rep.exit_code == 1 and not rep.clean and not rep.errors
    rules = {f.rule for f in rep.findings}
    assert {"SW010", "SW008"} <= rules
    for f in rep.findings:
        assert f.path.endswith("tpu_swirld/analysis/flow/audit.py")
        assert f.line > 0
    msgs = " ".join(f.message for f in rep.findings)
    assert "convert_element_type" in msgs     # the narrowing cast
    assert "int16" in msgs                    # pinpointed dtype
    # both findings land on the seeded line, not somewhere nearby
    assert len({f.line for f in rep.findings}) == 1


def test_mutation_dropped_clip_caught():
    rep = _audit("baseline", "dropped-clip")
    assert rep.exit_code == 1 and not rep.clean and not rep.errors
    assert {f.rule for f in rep.findings} == {"SW009"}
    (f,) = rep.findings
    assert f.path.endswith("tpu_swirld/analysis/flow/audit.py")
    assert "dynamic_slice" in f.message
    assert rep.mutation == "dropped-clip"


def test_mutations_are_never_suppressible():
    # the seeded defects live in audit.py, which must carry no
    # swirld-lint disables — otherwise the self-test could be silenced
    from tpu_swirld.analysis.lint import suppression_notes

    with open(os.path.join(
            _ROOT, "tpu_swirld", "analysis", "flow", "audit.py")) as fh:
        assert suppression_notes(fh.read()) == {}


def test_unknown_mutation_rejected():
    with pytest.raises(ValueError, match="unknown mutation"):
        scale_audit("baseline", mutate="nope")
    with pytest.raises(ValueError, match="unknown engines"):
        scale_audit("baseline", engines=["gpuzzz"])


# ----------------------------------------------------- suppressions


def test_suppression_requires_justification(tmp_path):
    src = (
        "a = t[i]  # swirld-lint: disable=SW009\n"
        "b = t[j]  # swirld-lint: disable=SW009 -- j is packer-clamped\n"
        "c = t[k]\n"
    )
    p = tmp_path / "frag.py"
    p.write_text(src)

    def fd(line):
        return Finding("SW009", RULE_NAMES["SW009"], str(p), line, 0,
                       "index not provably in bounds")

    kept, suppressed, unjustified = _apply_suppressions(
        [fd(1), fd(2), fd(3)])
    assert [f.line for f in kept] == [3]
    assert [(f.line, note) for f, note in suppressed] == \
        [(2, "j is packer-clamped")]
    assert [f.line for f in unjustified] == [1]
    assert "without justification" in unjustified[0].message


def test_suppression_wrong_rule_does_not_apply(tmp_path):
    p = tmp_path / "frag.py"
    p.write_text("a = t[i]  # swirld-lint: disable=SW008 -- wraps are ok\n")
    f = Finding("SW009", RULE_NAMES["SW009"], str(p), 1, 0, "oob")
    kept, suppressed, unjustified = _apply_suppressions([f])
    assert kept == [f] and not suppressed and not unjustified


# ----------------------------------------------------- envelopes (host side)


def test_envelope_presets():
    assert set(preset_names()) >= {"baseline", "1m", "custom"}
    env = get_envelope("custom", {"events": 123})
    assert env.events == 123 and env.name == "custom"
    with pytest.raises(ValueError, match="unknown envelope fields"):
        get_envelope("custom", {"eventz": 1})
    with pytest.raises(ValueError, match="unknown envelope"):
        get_envelope("2g")


def test_shipped_envelopes_pass_host_checks():
    assert not host_envelope_findings(get_envelope("baseline"))
    assert not host_envelope_findings(get_envelope("1m"))


def test_host_checks_catch_bad_envelopes():
    # a timestamp bound reaching the order sentinel must be SW011
    env = get_envelope("custom", {"t_max": INT32_MAX})
    assert "SW011" in {f.rule for f in host_envelope_findings(env)}
    # stake pushing 3*tot past int32 must be SW008
    env = get_envelope("custom", {"stake_max": 1 << 24})
    assert "SW008" in {f.rule for f in host_envelope_findings(env)}


# ----------------------------------------------------- CLI + stamp + gate


def test_cli_list_rules(capsys):
    assert audit_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("SW008", "SW009", "SW010", "SW011"):
        assert rid in out


def test_cli_clean_baseline_with_coverage(capsys):
    # the full CLI path: catalog + host checks + runtime coverage probe
    # (one engine keeps the probe's compile load out of the suite budget;
    # test_stage_coverage_no_gaps sweeps all four)
    assert audit_main(["--envelope", "baseline", "--engine", "batch"]) == 0
    assert "proven clean" in capsys.readouterr().out


def test_cli_mutation_exits_one(capsys):
    rc = audit_main(["--mutate", "dropped-clip", "--json"])
    assert rc == 1
    doc = json.loads(capsys.readouterr().out)
    assert doc["clean"] is False and doc["mutation"] == "dropped-clip"
    assert doc["findings"] and doc["findings"][0]["rule"] == "SW009"


def test_cli_unknown_primitive_exits_two(monkeypatch, capsys):
    def bad_build(env):
        @jax.jit
        def unmodeled(x):
            return lax.sin(x)
        return unmodeled, {}, [stages.ArgDecl((4,), np.float32)]

    spec = stages.StageSpec("synthetic.sin", "synthetic.sin",
                            ("batch",), bad_build)
    monkeypatch.setattr(stages, "specs_for_engines", lambda e: [spec])
    rc = audit_main(["--envelope", "baseline", "--no-coverage"])
    assert rc == 2
    assert "unknown primitive 'sin'" in capsys.readouterr().out


def test_scale_audit_stamp_shape():
    d = scale_audit_stamp("baseline")
    assert d["clean"] is True and d["envelope"] == "baseline"
    assert d["findings"] == 0 and d["errors"] == 0
    assert d["suppressed"] > 0
    assert d["engines"] == list(stages.ENGINES)
    # cached per process: bench stamps several artifacts per run
    assert scale_audit_stamp("baseline") == d


def test_bench_compare_refuses_dirty_or_missing_stamp():
    spec = importlib.util.spec_from_file_location(
        "bench_compare", os.path.join(_ROOT, "scripts", "bench_compare.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    clean = {"scale_audit": {"envelope": "baseline", "clean": True}}
    dirty = {"scale_audit": {"envelope": "baseline", "clean": False,
                             "findings": 2}}
    assert mod.scale_audit_gate(clean) is None
    assert "failed the scale audit" in mod.scale_audit_gate(dirty)
    assert "no scale_audit stamp" in mod.scale_audit_gate({})


def test_audit_report_render_and_dict():
    rep = _audit("baseline", "ssm-acc-int16")
    txt = rep.render()
    assert "mutate=ssm-acc-int16" in txt and "finding(s)" in txt
    doc = rep.to_dict()
    assert doc["exit_code"] == 1
    assert doc["specs"] == ["mutation.ssm-acc-int16"]
    assert doc["exercised"] == sorted(rep.exercised)

"""repro.lint: per-rule positive/negative fixtures, pragma suppression,
the repo self-scan-clean invariant, the CLI smoke, and the compiled-HLO
contract checker (pure helpers on toy inputs + the real 4-device cells
in a subprocess)."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

from repro.lint import engine, rules
from repro.lint.contracts import (check_compile_flat, check_inter_group,
                                  check_wire_budget, entry_param_dtypes,
                                  find_outer_tensors, WIRE_BUDGETS)

pytestmark = pytest.mark.lint

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def scan(src: str, rel: str):
    return engine.lint_source(textwrap.dedent(src), rel, rules.ALL_RULES)


def hits(src: str, rel: str, rule: str):
    return [f for f in scan(src, rel) if f.rule == rule]


# --------------------------------------------------------- rule fixtures
# one (positive fires, negative clean) pair per rule, at a rel path
# inside the rule's scope

RULE_FIXTURES = {
    "jax-api-drift": dict(
        rel="src/repro/core/x.py",
        positive="""
            import jax
            f = jax.shard_map(g, mesh=m, in_specs=s, out_specs=s)
        """,
        negative="""
            from repro.sharding import shard_map
            f = shard_map(g, mesh=m, in_specs=s, out_specs=s)
        """),
    "raw-cost-analysis": dict(
        rel="src/repro/launch/x.py",
        positive="""
            cost = compiled.cost_analysis() or {}
        """,
        negative="""
            from repro.roofline.hlo import xla_cost_analysis
            cost = xla_cost_analysis(compiled)
        """),
    "clock-discipline": dict(
        rel="src/repro/serve/x.py",
        positive="""
            import time
            def step(self):
                t0 = time.time()
        """,
        negative="""
            import time
            def step(self, clock=time.monotonic):
                t0 = clock()
        """),
    "atomic-publish": dict(
        rel="src/repro/serve/x.py",
        positive="""
            def save(path, data):
                with open(path, "wb") as f:
                    f.write(data)
        """,
        negative="""
            import os
            def save(path, tmp, data):
                with open(tmp, "wb") as f:
                    f.write(data)
                os.replace(tmp, path)
        """),
    "fault-site-registry": dict(
        rel="src/repro/serve/x.py",
        positive="""
            def put(self, uid):
                spec = self.fault_plan.fire("warm.corrupt", uid)
        """,
        negative="""
            from repro.faults.plan import WARM_CORRUPT
            def put(self, uid):
                spec = self.fault_plan.fire(WARM_CORRUPT, uid)
        """),
    "seeded-rng": dict(
        rel="src/repro/data/x.py",
        positive="""
            import numpy as np
            x = np.random.rand(4)
        """,
        negative="""
            import numpy as np
            rng = np.random.default_rng(0)
            x = rng.random(4)
        """),
    "static-aux-hashable": dict(
        rel="src/repro/serve/x.py",
        positive="""
            import jax
            jax.tree_util.register_pytree_node(
                T, lambda t: ((t.x,), [t.a, t.b]), lambda aux, ch: T(*ch))
        """,
        negative="""
            import jax
            jax.tree_util.register_pytree_node(
                T, lambda t: ((t.x,), (t.a, t.b)), lambda aux, ch: T(*ch))
        """),
}


@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_rule_fires_on_violation(rule):
    fx = RULE_FIXTURES[rule]
    found = hits(fx["positive"], fx["rel"], rule)
    assert found, f"{rule} missed its positive fixture"
    assert all(f.path == fx["rel"] and f.line > 0 for f in found)


@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_rule_quiet_on_clean_code(rule):
    fx = RULE_FIXTURES[rule]
    assert hits(fx["negative"], fx["rel"], rule) == []


@pytest.mark.parametrize("rule", sorted(RULE_FIXTURES))
def test_pragma_suppresses_each_rule(rule):
    fx = RULE_FIXTURES[rule]
    src = textwrap.dedent(fx["positive"])
    line = hits(fx["positive"], fx["rel"], rule)[0].line
    lines = src.splitlines()
    lines[line - 1] += f"  # lint: allow({rule}): fixture"
    assert [f for f in engine.lint_source("\n".join(lines), fx["rel"],
                                          rules.ALL_RULES)
            if f.rule == rule] == []


def test_standalone_pragma_covers_next_line():
    src = """
        import time
        def step(self):
            # lint: allow(clock-discipline): test fixture
            t0 = time.time()
    """
    assert hits(src, "src/repro/serve/x.py", "clock-discipline") == []


def test_pragma_without_reason_is_a_finding():
    # the reasonless pragma is assembled at runtime so THIS file's own
    # self-scan (pragmas are matched line-wise on raw source, strings
    # included) stays clean
    src = textwrap.dedent("""
        import time
        def step(self):
            t0 = time.time()  {} allow(clock-discipline)
    """).format("# lint:")
    found = engine.lint_source(src, "src/repro/serve/x.py", rules.ALL_RULES)
    assert any(f.rule == engine.BAD_PRAGMA_RULE for f in found)
    # and the unreasoned pragma does NOT suppress
    assert any(f.rule == "clock-discipline" for f in found)


def test_pragma_only_suppresses_named_rule():
    src = """
        import time
        def step(self):
            t0 = time.time()  # lint: allow(seeded-rng): wrong rule named
    """
    assert hits(src, "src/repro/serve/x.py", "clock-discipline")


# ------------------------------------------------------------- scoping

def test_clock_rule_ignores_reference_defaults():
    """time.monotonic as an injectable-clock DEFAULT is the contract, not
    a violation (episodic.py:568-style)."""
    src = """
        import time
        class Engine:
            def __init__(self, clock=None):
                self.clock = clock if clock is not None else time.monotonic
    """
    assert hits(src, "src/repro/serve/x.py", "clock-discipline") == []


def test_clock_rule_out_of_scope_elsewhere():
    src = "import time\nt0 = time.time()\n"
    assert hits(src, "src/repro/roofline/x.py", "clock-discipline") == []


def test_atomic_publish_ignores_read_and_update_modes():
    src = """
        def fetch(self, uid):
            with open(self._path(uid), "r+b") as f:
                return f.read()
    """
    assert hits(src, "src/repro/serve/x.py", "atomic-publish") == []


@pytest.mark.parametrize("keyword,fires", [("check_rep", True),
                                            ("check_vma", False)])
def test_drift_rule_flags_removed_shard_map_keyword(keyword, fires):
    src = f"""
        from repro.sharding import shard_map
        f = shard_map(g, mesh=m, in_specs=s, out_specs=s, {keyword}=False)
    """
    found = hits(src, "src/repro/core/x.py", "jax-api-drift")
    assert bool(found) == fires
    if fires:
        assert found[0].line == 3 and "check_vma" in found[0].message


def test_drift_rule_skips_the_shims_themselves():
    src = "import jax\nshard_map = jax.shard_map\n"
    assert hits(src, "src/repro/sharding/__init__.py", "jax-api-drift") == []
    assert hits(src, "src/repro/core/x.py", "jax-api-drift")


def test_fault_site_message_names_the_constant():
    fx = RULE_FIXTURES["fault-site-registry"]
    (f,) = hits(fx["positive"], fx["rel"], "fault-site-registry")
    assert "WARM_CORRUPT" in f.message


def test_unseeded_default_rng_is_a_finding():
    src = "import numpy as np\nrng = np.random.default_rng()\n"
    assert hits(src, "src/repro/data/x.py", "seeded-rng")


# --------------------------------------------------------- repo is clean

def test_repo_self_scan_clean():
    """The merged repo must carry zero findings — the rules describe the
    code as it actually is, with every exception pragma'd and reasoned."""
    root = engine.repo_root()
    findings = engine.lint_paths(engine.default_targets(root), root,
                                 rules.ALL_RULES)
    assert findings == [], "\n".join(f.format() for f in findings)


# ------------------------------------------------------------- CLI smoke

def _cli(args, cwd=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    return subprocess.run([sys.executable, "-m", "repro.lint"] + args,
                          capture_output=True, text=True, env=env,
                          cwd=cwd, timeout=540)


def test_cli_exit_zero_on_repo():
    r = _cli([])
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_nonzero_names_file_line_and_rule(tmp_path):
    bad = tmp_path / "src" / "repro" / "serve" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import time\n\nt0 = time.time()\n")
    r = _cli([str(bad)])
    assert r.returncode == 1
    assert "bad.py:3" in r.stdout and "clock-discipline" in r.stdout


def test_cli_json_output(tmp_path):
    bad = tmp_path / "src" / "repro" / "data" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import numpy as np\nx = np.random.rand(3)\n")
    r = _cli(["--json", str(bad)])
    assert r.returncode == 1
    (rec,) = json.loads(r.stdout)
    assert rec["rule"] == "seeded-rng" and rec["line"] == 2


def test_cli_rules_filter_and_catalog(tmp_path):
    bad = tmp_path / "src" / "repro" / "data" / "bad.py"
    bad.parent.mkdir(parents=True)
    bad.write_text("import numpy as np\nx = np.random.rand(3)\n")
    assert _cli(["--rules", "clock-discipline", str(bad)]).returncode == 0
    r = _cli(["--list-rules"])
    assert r.returncode == 0
    for rule in rules.ALL_RULES:
        assert rule.name in r.stdout


# ----------------------------------------------- contract checks (pure)

def test_check_inter_group_catches_wide_collective():
    per_kind = {"all-reduce": dict(result_bytes=1.0, wire_bytes=1.0,
                                   count=1.0, max_group=4)}
    assert check_inter_group(per_kind, group_size=2)
    assert check_inter_group(per_kind, group_size=4) == []


def test_check_wire_budget_slack():
    assert check_wire_budget(1000.0, 1000.0, "x") == []
    assert check_wire_budget(1600.0, 1000.0, "x")


def test_check_compile_flat():
    assert check_compile_flat(dict(adapt_compiles=2, predict_compiles=1),
                              n_buckets=2) == []
    bad = check_compile_flat(dict(adapt_compiles=5, predict_compiles=3),
                             n_buckets=2)
    assert len(bad) == 2


_TOY_HLO = textwrap.dedent("""\
    ENTRY %main (p0: {ptype}) -> {ptype} {{
      %p0 = {ptype} parameter(0)
      ROOT %n = {ptype} negate(%p0)
    }}
""")


def test_find_outer_tensors_toy_hlo():
    bad = _TOY_HLO.format(ptype="f32[2,16,16,16]")   # per-example: lead 32
    ok = _TOY_HLO.format(ptype="f32[2,3,16,16]")     # per-class: lead 6
    assert find_outer_tensors(bad, feature_dim=16, max_leading=6)
    assert find_outer_tensors(ok, feature_dim=16, max_leading=6) == []
    # non-square trailing dims are not outer blocks
    other = _TOY_HLO.format(ptype="f32[2,16,16,8]")
    assert find_outer_tensors(other, feature_dim=16, max_leading=6) == []


def test_entry_param_dtypes_toy_hlo():
    assert "s8" in entry_param_dtypes(_TOY_HLO.format(ptype="s8[4,4]"))
    assert "s8" not in entry_param_dtypes(_TOY_HLO.format(ptype="f32[4,4]"))


def test_budget_readers_match_checked_in_csvs():
    assert WIRE_BUDGETS["serve_small/weight_stationary"] == 15552.0
    assert WIRE_BUDGETS["serve_small/training"] == 117888.0
    assert WIRE_BUDGETS["replicas2_none/per_replica"] == 2560.0


# --------------------------------------- contract cells (4 fake devices)

def _run_4dev(args_or_code, timeout=540):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    if isinstance(args_or_code, str):
        cmd = [sys.executable, "-c", textwrap.dedent(args_or_code)]
    else:
        env["REPRO_LINT_CONTRACTS_WORKER"] = "1"
        cmd = [sys.executable, "-m", "repro.lint"] + args_or_code
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=timeout)


def test_contract_cells_pass_on_real_programs():
    """replica_2x2 + int8_ws compile the real serving programs on 4
    emulated devices and must satisfy every structural contract."""
    r = _run_4dev(["--no-ast", "--contracts",
                   "--cells", "replica_2x2", "--cells", "int8_ws"])
    assert r.returncode == 0, r.stdout + r.stderr[-3000:]


def test_contract_cells_engine_and_lite():
    r = _run_4dev(["--no-ast", "--contracts",
                   "--cells", "compile_flat", "--cells", "lite_outer"])
    assert r.returncode == 0, r.stdout + r.stderr[-3000:]


def test_contract_catches_deliberate_inter_group_violation():
    """A predict program deliberately compiled across the FULL 4-device
    mesh, audited as if it were a 2-device replica group: the checker
    must flag the group-spanning collective."""
    r = _run_4dev("""
        import jax, jax.numpy as jnp
        from repro.core.episodic_train import task_key
        from repro.core.lite import LiteSpec
        from repro.core.meta_learners import MetaLearnerConfig, make_learner
        from repro.core.set_encoder import SetEncoderConfig
        from repro.data.episodic import (EpisodicImageConfig,
                                         collate_task_batch,
                                         sample_image_task)
        from repro.models.conv_backbone import (ConvBackboneConfig,
                                                make_conv_backbone)
        from repro.roofline.hlo import collectives_report
        from repro.serve.quant_params import quantize_frozen
        from repro.lint.contracts import _compile_predict, check_inter_group

        lr = make_learner(
            MetaLearnerConfig(kind="protonets", way=3),
            make_conv_backbone(ConvBackboneConfig(widths=(8,),
                                                  feature_dim=16)),
            SetEncoderConfig(kind="conv", conv_blocks=1, conv_width=8,
                             task_dim=16))
        params = lr.init(jax.random.key(0))
        sw = quantize_frozen(lr, params, "none")
        ts = [sample_image_task(jax.random.key(i), EpisodicImageConfig(
            way=3, shot=5, query_per_class=4, image_size=8))
            for i in range(2)]
        batch = collate_task_batch(ts, support_size=16, query_size=12)
        keys = jax.vmap(lambda i: task_key(jax.random.key(0), i))(
            jnp.arange(2))
        states = lr.adapt_batch(params, batch, keys,
                                LiteSpec(exact=True, chunk_size=8))
        mesh = jax.make_mesh((4,), ("serve",))   # spans ALL 4 devices
        text = _compile_predict(lr, sw, states, batch.query_x, mesh,
                                "weight_stationary")
        rep = collectives_report(text)
        msgs = check_inter_group(rep["per_kind"], group_size=2)
        assert msgs, "4-device collective not flagged for a 2-wide group"
        assert "inter-group" in msgs[0]
        assert check_inter_group(rep["per_kind"], group_size=4) == []
        print("VIOLATION_CAUGHT")
        """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "VIOLATION_CAUGHT" in r.stdout


def test_contract_catches_eager_dequantization():
    """Serving weights dequantized OUTSIDE the jitted step (a persistent
    fp32 copy of the frozen slice) must fail the int8 residency check."""
    r = _run_4dev("""
        import jax, jax.numpy as jnp
        from repro.core.episodic_train import task_key
        from repro.core.lite import LiteSpec
        from repro.core.meta_learners import MetaLearnerConfig, make_learner
        from repro.core.set_encoder import SetEncoderConfig
        from repro.data.episodic import (EpisodicImageConfig,
                                         collate_task_batch,
                                         sample_image_task)
        from repro.models.conv_backbone import (ConvBackboneConfig,
                                                make_conv_backbone)
        from repro.serve.quant_params import (dequantize_params, param_bytes,
                                              quantize_frozen,
                                              ServingWeights)
        from repro.lint.contracts import check_int8_residency

        lr = make_learner(
            MetaLearnerConfig(kind="protonets", way=3),
            make_conv_backbone(ConvBackboneConfig(widths=(8,),
                                                  feature_dim=16)),
            SetEncoderConfig(kind="conv", conv_blocks=1, conv_width=8,
                             task_dim=16))
        params = lr.init(jax.random.key(0))
        sw = quantize_frozen(lr, params, "int8")
        # the violation: expand to fp32 eagerly and keep THAT resident
        eager = ServingWeights(tree=dequantize_params(sw),
                               quant_paths=sw.quant_paths,
                               native_paths=(), frozen_roots=sw.frozen_roots,
                               mode="none")
        ts = [sample_image_task(jax.random.key(i), EpisodicImageConfig(
            way=3, shot=5, query_per_class=4, image_size=8))
            for i in range(2)]
        batch = collate_task_batch(ts, support_size=16, query_size=12)
        keys = jax.vmap(lambda i: task_key(jax.random.key(0), i))(
            jnp.arange(2))
        states = lr.adapt_batch(eager.tree, batch, keys,
                                LiteSpec(exact=True, chunk_size=8))
        text = jax.jit(lambda w, st, qx: lr.predict_batch(
            w.tree, st, qx)).lower(eager, states, batch.query_x
                                   ).compile().as_text()
        msgs = check_int8_residency(text, eager, param_bytes(eager))
        assert any("s8" in m or "fp32" in m for m in msgs), msgs
        print("EAGER_CAUGHT")
        """)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "EAGER_CAUGHT" in r.stdout

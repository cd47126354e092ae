"""AST lint engine (analysis/ast_rules.py): every rule has a mutation test
(a synthetic violation it must flag) and a false-positive test (idiomatic
code it must NOT flag) — the analyzer is verified, not just green.
"""

import textwrap

import pytest

from distributed_pytorch_training_tpu.analysis.ast_rules import (
    AXIS_NAMES, FileContext, iter_source_files, run_ast_rules,
    traced_function_names,
)


def _lint(tmp_path, source, rules=None, name="mod.py"):
    path = tmp_path / name
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(source))
    return run_ast_rules(files=[path], rules=rules)


def _rules_of(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# shard-map-shim-only
# ---------------------------------------------------------------------------


class TestShardMapShimOnly:
    def test_mutation_every_import_form_flags(self, tmp_path):
        for src in (
            "import jax.experimental.shard_map\n",
            "from jax.experimental.shard_map import shard_map\n",
            "from jax.experimental import shard_map\n",
            "from jax.experimental import mesh_utils, shard_map\n",
            "from jax import shard_map\n",
            "import jax\nf = jax.shard_map(lambda x: x)\n",
            "import jax\nf = jax.experimental.shard_map.shard_map\n",
        ):
            findings = _lint(tmp_path, src, rules=["shard-map-shim-only"])
            assert findings, f"did not flag: {src!r}"

    def test_chained_attribute_use_reports_once(self, tmp_path):
        """`jax.experimental.shard_map.shard_map` is ONE use, not two —
        the inner Attribute chain must not double the finding count."""
        src = "import jax\nf = jax.experimental.shard_map.shard_map\n"
        findings = _lint(tmp_path, src, rules=["shard-map-shim-only"])
        assert len(findings) == 1, findings

    def test_mutation_check_rep_kwarg_outside_shim_flags(self, tmp_path):
        src = """
            from distributed_pytorch_training_tpu.parallel import shard_map
            f = shard_map(lambda x: x, mesh=None, in_specs=None,
                          out_specs=None, check_rep=False)
        """
        findings = _lint(tmp_path, src, rules=["shard-map-shim-only"])
        assert _rules_of(findings) == {"shard-map-shim-only"}
        assert "check_rep" in findings[0].message
        # the renamed flag is the same violation
        src_vma = src.replace("check_rep", "check_vma")
        assert _lint(tmp_path, src_vma, rules=["shard-map-shim-only"])

    def test_docstring_and_string_mentions_do_not_flag(self, tmp_path):
        """THE false-positive class the regex lint had (ISSUE 3 satellite):
        prose about the entry points is not a use of them."""
        src = '''
            """Module docs: jax.experimental.shard_map moved to
            jax.shard_map; never `from jax.experimental import shard_map`.
            """
            MSG = "use jax.shard_map via the shim"

            def f():
                """Docs quoting jax.experimental.shard_map.shard_map(...)."""
                return MSG  # comment: jax.shard_map is the new entry point
        '''
        assert _lint(tmp_path, src, rules=["shard-map-shim-only"]) == []

    def test_shim_import_from_parallel_is_clean(self, tmp_path):
        src = """
            from distributed_pytorch_training_tpu.parallel import shard_map
            g = shard_map(lambda x: x, mesh=None, in_specs=None,
                          out_specs=None)
        """
        assert _lint(tmp_path, src, rules=["shard-map-shim-only"]) == []


# ---------------------------------------------------------------------------
# no-impure-calls-in-traced
# ---------------------------------------------------------------------------


class TestImpureCallsInTraced:
    def test_mutation_time_random_nprandom_flag(self, tmp_path):
        src = """
            import time, random
            import numpy as np
            import jax

            def step(x):
                t = time.perf_counter()
                r = random.random()
                z = np.random.rand(3)
                return x + t + r + z.sum()

            f = jax.jit(step)
        """
        findings = _lint(tmp_path, src,
                         rules=["no-impure-calls-in-traced"])
        msgs = "\n".join(f.message for f in findings)
        assert len(findings) == 3, msgs
        assert "time.perf_counter" in msgs
        assert "random.random" in msgs
        assert "numpy.random.rand" in msgs

    def test_mutation_nested_and_decorated_and_from_imports(self, tmp_path):
        src = """
            import jax
            from functools import partial
            from time import time as now

            @partial(jax.jit, donate_argnums=(0,))
            def step(x):
                def inner(y):
                    return y * now()
                return inner(x)
        """
        findings = _lint(tmp_path, src,
                         rules=["no-impure-calls-in-traced"])
        assert len(findings) == 1 and "time.time" in findings[0].message

    def test_shard_map_body_by_name_is_traced(self, tmp_path):
        src = """
            import numpy as np
            from distributed_pytorch_training_tpu.parallel import shard_map

            def body(x):
                return x * np.random.rand()

            f = shard_map(body, mesh=None, in_specs=None, out_specs=None)
        """
        findings = _lint(tmp_path, src,
                         rules=["no-impure-calls-in-traced"])
        assert len(findings) == 1

    def test_pure_numpy_shape_math_and_untraced_calls_clean(self, tmp_path):
        src = """
            import time
            import numpy as np
            import jax

            def step(x):
                n = np.prod(np.shape(x)) or 1   # trace-time shape math: OK
                k = jax.random.fold_in(jax.random.PRNGKey(0), 1)  # pure
                return x.reshape(n) + jax.random.normal(k, (n,))

            f = jax.jit(step)

            def host_loop():
                return time.time()  # not traced: OK
        """
        assert _lint(tmp_path, src,
                     rules=["no-impure-calls-in-traced"]) == []


# ---------------------------------------------------------------------------
# no-host-sync-in-step
# ---------------------------------------------------------------------------


class TestHostSyncInStep:
    def test_mutation_item_float_device_get_flag(self, tmp_path):
        src = """
            import jax

            class Trainer:
                def _train_step_impl(self, state, batch):
                    loss = compute(state, batch)
                    host = loss.item()
                    also = float(loss)
                    got = jax.device_get(loss)
                    return host + also + got
        """
        findings = _lint(tmp_path, src, rules=["no-host-sync-in-step"],
                         name="training/loop.py")
        assert len(findings) == 3
        msgs = "\n".join(f.message for f in findings)
        assert ".item()" in msgs and "float()" in msgs \
            and "jax.device_get" in msgs

    def test_scoped_to_loop_py_and_step_paths_only(self, tmp_path):
        src_other = """
            def _train_step_impl(self, state):
                return float(state)
        """
        # same violation in another file: out of scope
        assert _lint(tmp_path, src_other, rules=["no-host-sync-in-step"],
                     name="training/other.py") == []
        # loop.py, but a print-boundary fetch OUTSIDE the step path: allowed
        src_epoch = """
            def train_epoch(self, state, batches):
                for b in batches:
                    state, metrics = self._train_step(state, b)
                return float(metrics)
        """
        assert _lint(tmp_path, src_epoch, rules=["no-host-sync-in-step"],
                     name="training/loop.py") == []
        # float(literal) in a step path is not a device sync
        src_lit = """
            def _eval_step_impl(self, state):
                return state * float(2)
        """
        assert _lint(tmp_path, src_lit, rules=["no-host-sync-in-step"],
                     name="training/loop.py") == []


# ---------------------------------------------------------------------------
# axis-name-registry
# ---------------------------------------------------------------------------


class TestAxisNameRegistry:
    def test_registry_matches_mesh_module(self):
        """The lint registry is import-free by design; it must stay the
        mirror of the real one (parallel/mesh.py AXIS_NAMES)."""
        from distributed_pytorch_training_tpu.parallel import mesh

        assert AXIS_NAMES == mesh.AXIS_NAMES == frozenset(mesh.AXIS_ORDER)

    def test_mutation_literals_in_axis_positions_flag(self, tmp_path):
        src = """
            from jax import lax
            from jax.sharding import PartitionSpec as P
            from distributed_pytorch_training_tpu.parallel.collectives import (
                all_gather, psum,
            )

            def body(x):
                a = lax.psum(x, "data")
                b = psum(x, ("data", "fsdp"))
                c = all_gather(x, axis_name="model")
                return a + b + c

            SPEC = P("data", None)
        """
        findings = _lint(tmp_path, src, rules=["axis-name-registry"])
        flagged = sorted(f.message.split("'")[1] for f in findings)
        assert flagged == ["data", "data", "data", "fsdp", "model"], findings

    def test_non_axis_positions_do_not_flag(self, tmp_path):
        src = """
            cfg = {"model": "resnet18", "seq": 16}

            def report(cfg):
                return cfg.get("model"), cfg["seq"], "data"

            def loss(x):
                return x.sum("data")  # not a collective call
        """
        assert _lint(tmp_path, src, rules=["axis-name-registry"]) == []


# ---------------------------------------------------------------------------
# no-bare-os-exit
# ---------------------------------------------------------------------------


class TestNoBareOsExit:
    def test_mutation_every_call_form_flags(self, tmp_path):
        """A synthetic os._exit in any import form must be caught — abrupt
        claim-holder death wedges the server-side TPU grant (observed
        live), so the primitive lives ONLY behind heartbeat.hard_exit."""
        for src in (
            "import os\nos._exit(1)\n",
            "import os as operating\noperating._exit(2)\n",
            "from os import _exit\n_exit(3)\n",
            # aliasing is the same hazard with one extra hop
            "import os\nex = os._exit\n",
        ):
            findings = _lint(tmp_path, src, rules=["no-bare-os-exit"])
            assert _rules_of(findings) == {"no-bare-os-exit"}, src

    def test_heartbeat_home_is_exempt(self, tmp_path):
        src = "import os\n\ndef hard_exit(code):\n    os._exit(code)\n"
        findings = _lint(tmp_path, src, rules=["no-bare-os-exit"],
                         name="resilience/heartbeat.py")
        assert findings == []

    def test_per_line_suppression_honored(self, tmp_path):
        src = ("import os\n"
               "os._exit(70)  # analysis: disable=no-bare-os-exit\n")
        assert _lint(tmp_path, src, rules=["no-bare-os-exit"]) == []

    def test_docstring_mentions_and_sys_exit_clean(self, tmp_path):
        src = '''
            import sys

            def stop():
                """Docs may say os._exit without tripping the rule."""
                sys.exit(1)  # a normal exit is not an abrupt one

            comment = "os._exit(70) as a string is prose, not a call"
        '''
        assert _lint(tmp_path, src, rules=["no-bare-os-exit"]) == []


# ---------------------------------------------------------------------------
# pallas-call-in-ops-only
# ---------------------------------------------------------------------------


class TestPallasCallInOpsOnly:
    def test_mutation_every_import_form_flags(self, tmp_path):
        """A raw pl.pallas_call outside ops/ ships an ungated kernel (no
        backend gate, no interpreter fallback) — every import form must be
        caught (ISSUE 6 satellite)."""
        for src in (
            "from jax.experimental import pallas as pl\n"
            "k = pl.pallas_call(None, out_shape=None)\n",
            "from jax.experimental.pallas import pallas_call\n"
            "k = pallas_call(None, out_shape=None)\n",
            "import jax.experimental.pallas as pl\n"
            "k = pl.pallas_call\n",  # aliasing: same escape, one extra hop
        ):
            findings = _lint(tmp_path, src,
                             rules=["pallas-call-in-ops-only"])
            assert _rules_of(findings) == {"pallas-call-in-ops-only"}, src

    def test_ops_home_is_exempt(self, tmp_path):
        src = ("from jax.experimental import pallas as pl\n"
               "k = pl.pallas_call(None, out_shape=None)\n")
        findings = _lint(
            tmp_path, src, rules=["pallas-call-in-ops-only"],
            name="distributed_pytorch_training_tpu/ops/mykernel.py")
        assert findings == []

    def test_lookalike_ops_dir_not_exempt(self, tmp_path):
        """Exact trailing-component match (the OS_EXIT_HOME convention): a
        future `somewhere_else/ops/` must not inherit the exemption."""
        src = ("from jax.experimental import pallas as pl\n"
               "k = pl.pallas_call(None, out_shape=None)\n")
        findings = _lint(tmp_path, src, rules=["pallas-call-in-ops-only"],
                         name="serving/ops/rogue.py")
        assert _rules_of(findings) == {"pallas-call-in-ops-only"}

    def test_docstring_mentions_and_suppression_clean(self, tmp_path):
        src = '''
            """Prose about pl.pallas_call is not a kernel escape."""
            from jax.experimental import pallas as pl

            grid = pl.BlockSpec  # other pallas APIs are not the kernel
            MSG = "wrap pl.pallas_call in ops/ behind a gate"
        '''
        assert _lint(tmp_path, src,
                     rules=["pallas-call-in-ops-only"]) == []
        suppressed = (
            "from jax.experimental import pallas as pl\n"
            "k = pl.pallas_call  "
            "# analysis: disable=pallas-call-in-ops-only\n")
        assert _lint(tmp_path, suppressed,
                     rules=["pallas-call-in-ops-only"]) == []

    def test_repo_ops_kernels_are_the_only_users(self):
        """The rule binds on the real tree: every pallas_call in the repo
        lives under the package's ops/ (flash/ring/ulysses attention, the
        fused quantize codecs)."""
        assert run_ast_rules(rules=["pallas-call-in-ops-only"]) == []


# ---------------------------------------------------------------------------
# experiments-is-a-leaf
# ---------------------------------------------------------------------------


class TestExperimentsIsALeaf:
    def test_mutation_every_import_form_flags(self, tmp_path):
        """The forms the serving CLI and telemetry/device.py used until
        PR 30, and the absolute ones: each is found in a module of the
        package, none in experiments/ itself, a root script or a test."""
        pkg = "distributed_pytorch_training_tpu"
        rule = ["experiments-is-a-leaf"]
        relative = (
            "from ..experiments.harness import build_slot_engine\n",
            "def f():\n    from ..experiments import flops\n",
            "from .. import telemetry, experiments\n",
        )
        absolute = (
            f"import {pkg}.experiments.scaling\n",
            f"from {pkg}.experiments.harness import timed_steps\n",
            f"from {pkg} import experiments as ex\n",
        )
        for src in relative + absolute:
            found = _lint(tmp_path, src, rules=rule,
                          name=f"{pkg}/serving/rogue.py")
            assert _rules_of(found) == {"experiments-is-a-leaf"}, src
            assert _lint(tmp_path, src, rules=rule,
                         name=f"{pkg}/experiments/scaling.py") == []
        for src in absolute:
            for entry_point in ("train.py", "tests/test_rogue.py"):
                assert _lint(tmp_path, src, rules=rule,
                             name=entry_point) == []
        # its neighbours and lookalikes are no findings
        clean = ("from ..telemetry.trace_analysis import collective_share\n"
                 "from .build import build_slot_engine\n"
                 "from experiments import something_else\n"
                 'OUT = "./experiments"  # the run-output directory\n')
        assert _lint(tmp_path, clean, rules=rule,
                     name=f"{pkg}/serving/fine.py") == []

    def test_the_package_does_not_stand_on_experiments(self):
        """Binds on the real tree (it did not before PR 30: serving/__main__
        built its engine through experiments.harness and telemetry/device
        parsed captures through experiments.trace_analysis)."""
        assert run_ast_rules(rules=["experiments-is-a-leaf"]) == []


# ---------------------------------------------------------------------------
# profiler-session-via-stepprofiler-only
# ---------------------------------------------------------------------------


class TestProfilerSessionHome:
    RULE = ["profiler-session-via-stepprofiler-only"]

    def test_mutation_every_use_form_flags(self, tmp_path):
        for src in (
            "import jax\njax.profiler.start_trace('/tmp/t')\n",
            "import jax\njax.profiler.stop_trace()\n",
            "import jax\nst = jax.profiler.start_trace\nst('/tmp/t')\n",
            "from jax.profiler import start_trace\nstart_trace('/tmp/t')\n",
            "from jax.profiler import stop_trace as halt\nhalt()\n",
        ):
            findings = _lint(tmp_path, src, rules=self.RULE)
            assert findings, f"did not flag: {src!r}"
            assert _rules_of(findings) == set(self.RULE)

    def test_profiling_home_is_exempt(self, tmp_path):
        src = ("import jax\n\ndef open_session(d):\n"
               "    jax.profiler.start_trace(d)\n")
        assert _lint(tmp_path, src, rules=self.RULE,
                     name="utils/profiling.py") == []
        # exact path-component match: lookalikes must not inherit it
        assert _lint(tmp_path, src, rules=self.RULE,
                     name="myutils/profiling.py") != []
        assert _lint(tmp_path, src, rules=self.RULE,
                     name="utils/my_profiling.py") != []

    def test_docstring_mentions_and_other_profiler_api_clean(self,
                                                             tmp_path):
        src = '''
            """Docs may say jax.profiler.start_trace freely."""
            import jax

            def annotate(name):
                # other jax.profiler API is not a session entry point
                return jax.profiler.TraceAnnotation(name)
        '''
        assert _lint(tmp_path, src, rules=self.RULE) == []
        suppressed = (
            "import jax\njax.profiler.start_trace('/t')  "
            "# analysis: disable=profiler-session-via-stepprofiler-only\n")
        assert _lint(tmp_path, suppressed, rules=self.RULE) == []

    def test_repo_profiling_is_the_only_user(self):
        """The rule binds on the real tree: every raw session entry in
        the repo lives in utils/profiling.py (trace_analysis's
        capture_step_trace migrated onto trace_session)."""
        assert run_ast_rules(rules=self.RULE) == []


# ---------------------------------------------------------------------------
# control-decisions-gated (ISSUE 20)
# ---------------------------------------------------------------------------


class TestControlDecisionsGated:
    RULE = ["control-decisions-gated"]

    def test_mutation_every_reference_form_flags(self, tmp_path):
        """A control/ policy module touching the re-plan surface is the
        gate bypass this rule exists for — attribute calls, bare names
        from imports, AND bound-method aliasing (the one-extra-hop
        bypass) must all flag."""
        for src in (
            "def decide(sup, report, state):\n"
            "    return sup.boundary_shrink(report, state, epoch=0,"
            " step=1)\n",
            "def decide(sup, report, state):\n"
            "    return sup.boundary_retune(report, state, epoch=0,"
            " step=1, overrides={})\n",
            "from ..resilience.elastic import reshard_train_state\n"
            "def decide(state):\n"
            "    return reshard_train_state(state, 8, 4, None, None)\n",
            "from ..resilience.elastic import plan_elastic_world\n"
            "W = plan_elastic_world(7, 16)\n",
            "def decide(sup):\n"
            "    commit = sup.boundary_shrink\n"   # aliasing is the same
            "    return commit\n",                  # bypass
            "def decide(sup, report, state, epoch, step):\n"
            "    return sup._maybe_grow(report, state, epoch, step)\n",
            "def decide(sup):\n"
            "    return sup.replan_cb(4)\n",
        ):
            findings = _lint(tmp_path, src, rules=self.RULE,
                             name="control/policy.py")
            assert findings, f"did not flag: {src!r}"
            assert _rules_of(findings) == set(self.RULE)

    def test_apply_home_is_exempt(self, tmp_path):
        """control/apply.py IS the one sanctioned entry — the same code
        there is clean; a lookalike directory must not inherit the
        exemption."""
        src = ("def _apply_evict(sup, report, state):\n"
               "    return sup.boundary_shrink(report, state, epoch=0,"
               " step=1)\n")
        assert _lint(tmp_path, src, rules=self.RULE,
                     name="control/apply.py") == []
        assert _lint(tmp_path, src, rules=self.RULE,
                     name="mycontrol/apply.py") == []   # not a control/ dir
        assert _lint(tmp_path, src, rules=self.RULE,
                     name="control/apply_helpers.py") != []

    def test_outside_control_is_out_of_scope(self, tmp_path):
        """The Supervisor and the elastic module CALL this surface —
        that is their job; the rule binds only inside control/."""
        src = ("def run(sup, report, state):\n"
               "    return sup.boundary_shrink(report, state, epoch=0,"
               " step=1)\n")
        assert _lint(tmp_path, src, rules=self.RULE,
                     name="resilience/supervisor_helper.py") == []

    def test_docstring_mentions_do_not_flag(self, tmp_path):
        src = '''
            """Policies PROPOSE; control/apply.py commits via
            Supervisor.boundary_shrink / boundary_retune after the
            contract gate (reshard_train_state, plan_elastic_world)."""
            NOTE = "see boundary_retune for the apply path"

            def propose():
                """Docs quoting replan_cb(survivors) are not a call."""
                return NOTE
        '''
        assert _lint(tmp_path, src, rules=self.RULE,
                     name="control/notes.py") == []

    def test_repo_control_package_is_clean(self):
        """The rule binds on the real tree: every re-plan reference in
        control/ lives in apply.py."""
        assert run_ast_rules(rules=self.RULE) == []


# ---------------------------------------------------------------------------
# engine mechanics
# ---------------------------------------------------------------------------


class TestTelemetryEmitOutsideTraced:
    RULE = ["telemetry-emit-outside-traced"]

    def test_mutation_every_import_form_flags(self, tmp_path):
        header = "import jax\n"
        footer = "jax.jit(step)\n"
        for body in (
            # absolute package import, attribute call
            "from distributed_pytorch_training_tpu import telemetry\n"
            "def step(x):\n    telemetry.counter('bad', 1)\n    return x\n",
            # relative module import (the repo's own idiom)
            "from .. import telemetry\n"
            "def step(x):\n    telemetry.span_event('bad', 0.1)\n"
            "    return x\n",
            # member from-import, relative
            "from ..telemetry import span_event\n"
            "def step(x):\n    span_event('bad', 0.1)\n    return x\n",
            # member from a submodule, absolute, aliased
            "from distributed_pytorch_training_tpu.telemetry.recorder "
            "import counter as c\n"
            "def step(x):\n    c('bad', 1)\n    return x\n",
            # plain-import alias
            "import distributed_pytorch_training_tpu.telemetry as tel\n"
            "def step(x):\n    tel.emit('event', 'bad')\n    return x\n",
            # unaliased dotted import, full-path call
            "import distributed_pytorch_training_tpu.telemetry\n"
            "def step(x):\n"
            "    distributed_pytorch_training_tpu.telemetry.emit('e', 'b')\n"
            "    return x\n",
        ):
            findings = _lint(tmp_path, header + body + footer,
                             rules=self.RULE)
            assert _rules_of(findings) == set(self.RULE), \
                f"did not flag: {body!r}"

    def test_shard_map_body_flags_too(self, tmp_path):
        src = """
            import jax
            from distributed_pytorch_training_tpu.parallel import shard_map
            from .. import telemetry
            def body(x):
                telemetry.gauge('depth', 1)
                return x
            f = shard_map(body, None, in_specs=(), out_specs=())
        """
        findings = _lint(tmp_path, src, rules=self.RULE)
        assert _rules_of(findings) == set(self.RULE)

    def test_host_side_emission_is_clean(self, tmp_path):
        """The instrumented loop's real shape: spans AROUND the dispatched
        step (train_epoch is not traced) never flag, nor do docstring
        mentions inside traced bodies."""
        src = '''
            import jax
            from .. import telemetry
            def _train_step_impl(state, batch):
                """telemetry.counter is forbidden here (a mention, not a
                call)."""
                return state
            step = jax.jit(_train_step_impl)
            def train_epoch(state, batches):
                for batch in batches:
                    with telemetry.span("step_dispatch"):
                        state = step(state, batch)
                telemetry.counter("steps", 1)
                return state
        '''
        assert _lint(tmp_path, src, rules=self.RULE) == []

    def test_unaliased_dotted_import_does_not_taint_package_root(
            self, tmp_path):
        """`import pkg.telemetry` binds only the root name `pkg` — a call
        to pkg.parallel.psum(...) inside a traced body is NOT a telemetry
        emit (the root-alias false positive the dotted-prefix matching
        exists to prevent)."""
        src = (
            "import jax\n"
            "import distributed_pytorch_training_tpu.telemetry\n"
            "def step(x):\n"
            "    return distributed_pytorch_training_tpu.parallel"
            ".collectives.psum(x, axis)\n"
            "jax.jit(step)\n")
        assert _lint(tmp_path, src, rules=self.RULE) == []

    def test_unrelated_telemetry_name_is_clean(self, tmp_path):
        """A user-defined object that happens to be NAMED telemetry (no
        import binding it to the package) is not the rule's business."""
        src = """
            import jax
            class Telemetry:
                def counter(self, *a): ...
            telemetry = Telemetry()
            def step(x):
                return x
            jax.jit(step)
            telemetry.counter('outside', 1)
        """
        assert _lint(tmp_path, src, rules=self.RULE) == []

    def test_per_line_suppression_honored(self, tmp_path):
        src = (
            "import jax\nfrom .. import telemetry\n"
            "def step(x):\n"
            "    telemetry.counter('x', 1)  "
            "# analysis: disable=telemetry-emit-outside-traced\n"
            "    return x\n"
            "jax.jit(step)\n")
        assert _lint(tmp_path, src, rules=self.RULE) == []


class TestEngine:
    def test_suppression_comment_skips_finding(self, tmp_path):
        src = """
            from jax import lax

            def body(x):
                a = lax.psum(x, "data")  # analysis: disable=axis-name-registry
                b = lax.pmean(x, "data")  # analysis: disable=all
                c = lax.pmax(x, "data")
                return a + b + c
        """
        findings = _lint(tmp_path, src, rules=["axis-name-registry"])
        assert len(findings) == 1
        assert findings[0].location.endswith(":7")

    def test_syntax_error_is_a_finding_not_a_crash(self, tmp_path):
        findings = _lint(tmp_path, "def broken(:\n")
        assert _rules_of(findings) == {"parse-error"}

    def test_unknown_rule_name_raises(self, tmp_path):
        with pytest.raises(KeyError, match="no-such-rule"):
            _lint(tmp_path, "x = 1\n", rules=["no-such-rule"])

    def test_traced_name_discovery(self, tmp_path):
        path = tmp_path / "t.py"
        path.write_text(textwrap.dedent("""
            import jax
            from distributed_pytorch_training_tpu.parallel import shard_map

            class T:
                def __init__(self):
                    self._step = jax.jit(self._step_impl, donate_argnums=(0,))

                def _step_impl(self, s):
                    return s

            g = shard_map(lambda x: x, mesh=None, in_specs=None,
                          out_specs=None)

            @jax.jit
            def decorated(x):
                return x
        """))
        names = traced_function_names(FileContext.parse(path))
        assert {"_step_impl", "decorated"} <= names

    def test_source_file_set_covers_package_and_scripts_not_tests(self):
        files = {p.name for p in iter_source_files()}
        assert "loop.py" in files and "chip_smoke.py" in files \
            and "train.py" in files
        assert "test_analysis_ast.py" not in files


class TestSpanNamesRegistered:
    """ISSUE 14 satellite: every span name emitted in-repo must appear in
    the recorder's registry — `telemetry summary` silently buckets
    unknown names into 'unaccounted', so a typo'd span VANISHES from the
    split instead of failing loudly."""

    RULE = ["span-names-registered"]

    def test_mutation_unregistered_literal_flags(self, tmp_path):
        for src in (
            # module-attribute form, context manager
            "from .. import telemetry\n"
            "with telemetry.span('rogue_phase'):\n    pass\n",
            # span_event hot-loop form
            "from .. import telemetry\n"
            "telemetry.span_event('also_rogue', 0.1)\n",
            # member import
            "from ..telemetry import span_event\n"
            "span_event('rogue_member', 0.1, step=3)\n",
            # ALIASED member import (the pallas rule's alias-aware bar)
            "from ..telemetry import span_event as se\n"
            "se('aliased_rogue', 0.1)\n",
            "from distributed_pytorch_training_tpu.telemetry.recorder "
            "import span as s\n"
            "s('aliased_rogue_2')\n",
            # unaliased dotted import
            "import distributed_pytorch_training_tpu.telemetry\n"
            "distributed_pytorch_training_tpu.telemetry"
            ".span('dotted_rogue')\n",
        ):
            findings = _lint(tmp_path, src, rules=self.RULE)
            assert _rules_of(findings) == set(self.RULE), \
                f"did not flag: {src!r}"

    def test_mutation_dynamic_name_flags(self, tmp_path):
        src = ("from .. import telemetry\n"
               "nm = 'x'\n"
               "telemetry.span(nm)\n")
        findings = _lint(tmp_path, src, rules=self.RULE)
        assert _rules_of(findings) == set(self.RULE)
        assert "dynamic span name" in findings[0].message

    def test_registered_names_and_other_emits_are_clean(self, tmp_path):
        src = """
            from .. import telemetry
            with telemetry.span("step_dispatch", epoch=0):
                pass
            telemetry.span_event("data_wait", 0.1, step=0)
            telemetry.span_event("prefill", 0.1)
            with telemetry.span("elastic_grow"):
                pass
            with telemetry.span("compile", program="decode"):
                pass
            telemetry.counter("any_counter_name", 1)   # counters are free
            telemetry.gauge("any_gauge_name", 1)
            MSG = "telemetry.span('prose_mention') in a string is fine"
        """
        assert _lint(tmp_path, src, rules=self.RULE) == []

    def test_suppression_and_no_import_are_clean(self, tmp_path):
        suppressed = (
            "from .. import telemetry\n"
            "telemetry.span('rogue')  "
            "# analysis: disable=span-names-registered\n")
        assert _lint(tmp_path, suppressed, rules=self.RULE) == []
        # a local object named `span` with no telemetry import bound
        unbound = "def span(n):\n    return n\nspan('whatever')\n"
        assert _lint(tmp_path, unbound, rules=self.RULE) == []

    def test_registry_matches_the_recorder(self):
        """The rule reads the REAL registry (one definition): every
        canonical tuple is included."""
        from distributed_pytorch_training_tpu.analysis.ast_rules import (
            _registered_span_names,
        )
        from distributed_pytorch_training_tpu.telemetry.recorder import (
            AUX_SPAN_NAMES, ELASTIC_SPAN_NAMES, SERVING_SPAN_NAMES,
            SPAN_NAMES,
        )

        reg = _registered_span_names()
        assert set(SPAN_NAMES) <= reg
        assert set(SERVING_SPAN_NAMES) <= reg
        assert set(ELASTIC_SPAN_NAMES) <= reg
        assert set(AUX_SPAN_NAMES) <= reg

    def test_repo_emits_only_registered_names(self):
        """The rule binds on the real tree: every span emission in the
        package + scripts uses a registered name today."""
        assert run_ast_rules(rules=["span-names-registered"]) == []


@pytest.mark.slow  # ~6 s; strictly redundant with the check --json gate in test_analysis_cli, which runs every AST rule over the repo
def test_repo_is_clean_under_every_ast_rule():
    """The tier-1 gate for the source-level contracts: the package and the
    top-level scripts carry zero violations (suppressions included)."""
    findings = run_ast_rules()
    assert not findings, "\n".join(str(f) for f in findings)

"""sparklint (tools/analysis) — per-rule fixture pairs + framework behavior.

Each rule gets a violating snippet and a clean one, written into a tmp tree
shaped like the repo (the rules scope themselves by repo-relative globs, so
the same rule code runs unchanged here and on the real tree). On top:
suppression handling (justified disables silence, unjustified disables are
themselves findings), JSON output schema, CLI exit codes — and the
acceptance gate: the real tree must lint clean.
"""

import json
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.analysis import run  # noqa: E402
from tools.analysis.__main__ import main  # noqa: E402


def make_tree(tmp_path, files):
    for rel, content in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(content))
    return tmp_path


def rule_ids(tmp_path, files, rules=None):
    return [f.rule for f in run(make_tree(tmp_path, files), rules=rules)]


# ---------------------------------------------------------------- rule 1

FOLD_BAD = """
    import jax.numpy as jnp

    def kern(s, m_prev):
        m = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m[:, None])
        return p
"""

FOLD_CLEAN = """
    from repro.kernels.common import online_fold

    def kern(s, v, acc_ref, m_ref, l_ref):
        online_fold(s, v, acc_ref, m_ref, l_ref, acc_dtype="float32")
"""


def test_fold_rule_flags_inline_exp(tmp_path):
    ids = rule_ids(tmp_path, {"src/repro/kernels/newkern.py": FOLD_BAD},
                   rules=["no-inline-softmax-fold"])
    assert ids == ["no-inline-softmax-fold"]


def test_fold_rule_clean_when_routed(tmp_path):
    ids = rule_ids(tmp_path, {"src/repro/kernels/newkern.py": FOLD_CLEAN},
                   rules=["no-inline-softmax-fold"])
    assert ids == []


def test_fold_rule_exempts_canonical_homes(tmp_path):
    files = {
        "src/repro/kernels/common.py": """
            import jax.numpy as jnp

            def online_fold(s, v, acc_ref, m_ref, l_ref):
                p = jnp.exp(s - m_ref[:, 0][:, None])
                return p
        """,
        "src/repro/core/online_softmax.py": """
            import jax.numpy as jnp

            def update(state, s, v):
                return jnp.exp(s - state[0])
        """,
    }
    assert rule_ids(tmp_path, files, rules=["no-inline-softmax-fold"]) == []


# ---------------------------------------------------------------- rule 2

LAUNCH_BAD = """
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def wrapper(kernel, interpret):
        return pl.pallas_call(
            kernel, grid=(1,),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)))
"""

LAUNCH_BARE = """
    from jax.experimental import pallas as pl

    def wrapper(kernel):
        return pl.pallas_call(kernel, grid=(1,))
"""

LAUNCH_CLEAN = """
    from jax.experimental import pallas as pl
    from repro.kernels.common import mosaic_kwargs

    def wrapper(kernel, interpret):
        return pl.pallas_call(kernel, grid=(1,),
                              **mosaic_kwargs(interpret, ("parallel",)))

    def wrapper2(kernel, interpret):
        kwargs = mosaic_kwargs(interpret, ("parallel",))
        return pl.pallas_call(kernel, grid=(1,), **kwargs)
"""


def test_launch_rule_flags_inline_params(tmp_path):
    ids = rule_ids(tmp_path, {"src/repro/kernels/k.py": LAUNCH_BAD},
                   rules=["mosaic-kwargs-launch"])
    # inline compiler_params AND missing helper: two findings on one call
    assert ids == ["mosaic-kwargs-launch"] * 2


def test_launch_rule_flags_bare_call(tmp_path):
    ids = rule_ids(tmp_path, {"src/repro/kernels/k.py": LAUNCH_BARE},
                   rules=["mosaic-kwargs-launch"])
    assert ids == ["mosaic-kwargs-launch"]


def test_launch_rule_clean_both_forms(tmp_path):
    ids = rule_ids(tmp_path, {"src/repro/kernels/k.py": LAUNCH_CLEAN},
                   rules=["mosaic-kwargs-launch"])
    assert ids == []


NAMED_CLEAN = """
    from jax.experimental import pallas as pl
    from repro.kernels.common import mosaic_kwargs

    def wrapper(kernel, interpret, name):
        return pl.pallas_call(kernel, grid=(1,), name=name,
                              **mosaic_kwargs(interpret, ("parallel",)))
"""


@pytest.mark.parametrize("source,expect", [
    (LAUNCH_BARE, ["pallas-call-named"]), (LAUNCH_BAD, ["pallas-call-named"]),
    (NAMED_CLEAN, [])], ids=["bare", "inline-params", "named"])
def test_named_rule_flags_an_unnamed_kernel(tmp_path, source, expect):
    ids = rule_ids(tmp_path, {"src/repro/kernels/k.py": source},
                   rules=["pallas-call-named"])
    assert ids == expect


# ---------------------------------------------------------------- rule 3

ACC_BAD = """
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    SCRATCH = pltpu.VMEM((8, 128), jnp.bfloat16)

    def kern(acc_ref, pv, alpha):
        acc_ref[...] = (acc_ref[...] * alpha).astype(jnp.float16) + pv
"""

ACC_CLEAN = """
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    SCRATCH = pltpu.VMEM((8, 128), jnp.float32)

    def kern(acc_ref, pv, alpha):
        acc_ref[...] = acc_ref[...] * alpha + pv.astype(jnp.float32)
"""


def test_f32_rule_flags_downcasts(tmp_path):
    ids = rule_ids(tmp_path, {"src/repro/kernels/k.py": ACC_BAD},
                   rules=["f32-accumulators"])
    assert ids == ["f32-accumulators"] * 2      # bf16 scratch + f16 store


def test_f32_rule_clean(tmp_path):
    ids = rule_ids(tmp_path, {"src/repro/kernels/k.py": ACC_CLEAN},
                   rules=["f32-accumulators"])
    assert ids == []


# ---------------------------------------------------------------- rule 4

MASK_BAD = """
    import jax.numpy as jnp

    NEG = -1e9

    def mask(s, allowed):
        s = jnp.where(allowed, s, -jnp.inf)
        return jnp.where(allowed, s, float("-inf"))
"""

MASK_CLEAN = """
    import jax.numpy as jnp
    from repro.core.online_softmax import NEG_INF

    def mask(s, allowed):
        return jnp.where(allowed, s, NEG_INF)
"""


def test_mask_rule_flags_local_constants(tmp_path):
    ids = rule_ids(tmp_path, {"src/repro/models/m.py": MASK_BAD},
                   rules=["shared-mask-constant"])
    assert ids == ["shared-mask-constant"] * 3


def test_mask_rule_clean_and_definition_site_exempt(tmp_path):
    files = {
        "src/repro/models/m.py": MASK_CLEAN,
        "src/repro/core/online_softmax.py": "NEG_INF = -1e30\n",
    }
    assert rule_ids(tmp_path, files, rules=["shared-mask-constant"]) == []


# ---------------------------------------------------------------- rule 5

HOST_BAD = """
    import numpy as np
    import jax.numpy as jnp

    def schedule(queue):
        return jnp.asarray(queue)
"""

HOST_FROM_BAD = """
    from jax import numpy as jnp
"""

HOST_CLEAN = """
    import numpy as np

    def schedule(queue):
        return np.asarray(queue)
"""


def test_host_rule_flags_jax_imports(tmp_path):
    ids = rule_ids(tmp_path, {"src/repro/serving/scheduler.py": HOST_BAD,
                              "src/repro/serving/drafter.py": HOST_FROM_BAD},
                   rules=["host-layer-numpy-only"])
    assert ids == ["host-layer-numpy-only"] * 2


def test_host_rule_clean_and_engine_exempt(tmp_path):
    files = {"src/repro/serving/paged_cache.py": HOST_CLEAN,
             "src/repro/serving/engine.py": "import jax\n"}
    assert rule_ids(tmp_path, files, rules=["host-layer-numpy-only"]) == []


def test_host_rule_covers_state_cache(tmp_path):
    """The recurrent-state slot cache is host bookkeeping too."""
    ids = rule_ids(tmp_path, {"src/repro/serving/state_cache.py": HOST_BAD},
                   rules=["host-layer-numpy-only"])
    assert ids == ["host-layer-numpy-only"]


# ---------------------------------------------------------------- rule 6

DONATE_BAD = """
    import jax

    def make():
        def decode_fn(params, token, caches):
            return caches

        return jax.jit(decode_fn)
"""

DONATE_USE_BAD = """
    import jax

    def make():
        def decode_fn(params, caches):
            return caches

        step = jax.jit(decode_fn, donate_argnums=(1,))

        def drive(params, caches):
            out = step(params, caches)
            return out, caches
        return drive
"""

DONATE_CLEAN = """
    import jax

    def make():
        def decode_fn(params, token, caches):
            return caches

        step = jax.jit(decode_fn, donate_argnums=(2,))

        def drive(params, token, caches):
            caches = step(params, token, caches)
            return caches
        return drive
"""


def test_donate_rule_flags_undonated_pool(tmp_path):
    ids = rule_ids(tmp_path, {"src/repro/runtime/steps.py": DONATE_BAD},
                   rules=["donate-page-pool"])
    assert ids == ["donate-page-pool"]


def test_donate_rule_flags_read_after_donation(tmp_path):
    fs = run(make_tree(tmp_path,
                       {"src/repro/runtime/steps.py": DONATE_USE_BAD}),
             rules=["donate-page-pool"])
    assert [f.rule for f in fs] == ["donate-page-pool"]
    assert "read after being donated" in fs[0].message


def test_donate_rule_clean_rebind(tmp_path):
    ids = rule_ids(tmp_path, {"src/repro/runtime/steps.py": DONATE_CLEAN},
                   rules=["donate-page-pool"])
    assert ids == []


# ---------------------------------------------------------------- rule 7

FSDP_BAD = """
    from repro.configs import ArchConfig

    CONFIG = ArchConfig(name="x", sharding_profile="fsdp")
"""

FSDP_CLEAN = """
    from repro.configs import ArchConfig

    CONFIG = ArchConfig(name="x", sharding_profile="fsdp", fsdp=True)
    OTHER = ArchConfig(name="y", sharding_profile="tp_sp")
"""


def test_fsdp_rule_flags_annotation_alone(tmp_path):
    ids = rule_ids(tmp_path, {"src/repro/configs/x.py": FSDP_BAD},
                   rules=["fsdp-profile-gate"])
    assert ids == ["fsdp-profile-gate"]


def test_fsdp_rule_clean_with_flag(tmp_path):
    ids = rule_ids(tmp_path, {"src/repro/configs/x.py": FSDP_CLEAN},
                   rules=["fsdp-profile-gate"])
    assert ids == []


# ---------------------------------------------------------------- rule 8

OPS_FIXTURE = """
    def covered(q, k, v):
        return q

    def uncovered(q, k, v):
        return k

    def _private(q):
        return q
"""

TEST_FIXTURE = """
    from repro.kernels import ops

    def test_covered():
        assert ops.covered(1, 2, 3) == 1
"""


def test_ops_coverage_flags_untested_entrypoint(tmp_path):
    fs = run(make_tree(tmp_path, {"src/repro/kernels/ops.py": OPS_FIXTURE,
                                  "tests/test_ops.py": TEST_FIXTURE}),
             rules=["ops-test-coverage"])
    assert [f.rule for f in fs] == ["ops-test-coverage"]
    assert "uncovered" in fs[0].message


def test_ops_coverage_clean_when_referenced(tmp_path):
    files = {"src/repro/kernels/ops.py": OPS_FIXTURE,
             "tests/test_ops.py": TEST_FIXTURE
             + "\n    def test_more():\n        ops.uncovered(1, 2, 3)\n"}
    assert rule_ids(tmp_path, files, rules=["ops-test-coverage"]) == []


# ---------------------------------------------------------------- rule 9

ARCHS_FIXTURE = """
    ARCHS = [
        "alpha_1b", "beta_2b",
    ]
"""

ZOO_FIXTURE = """
    import pytest

    @pytest.mark.parametrize("arch", ["alpha_1b"])
    def test_engine_matches_oracle(arch):
        assert arch
"""


def test_zoo_coverage_flags_unserved_config(tmp_path):
    fs = run(make_tree(tmp_path,
                       {"src/repro/configs/__init__.py": ARCHS_FIXTURE,
                        "tests/test_config_zoo.py": ZOO_FIXTURE}),
             rules=["config-zoo-coverage"])
    assert [f.rule for f in fs] == ["config-zoo-coverage"]
    assert "beta_2b" in fs[0].message


def test_zoo_coverage_flags_missing_matrix(tmp_path):
    fs = run(make_tree(tmp_path,
                       {"src/repro/configs/__init__.py": ARCHS_FIXTURE}),
             rules=["config-zoo-coverage"])
    assert [f.rule for f in fs] == ["config-zoo-coverage"]
    assert "missing" in fs[0].message


def test_zoo_coverage_clean_when_every_config_named(tmp_path):
    files = {"src/repro/configs/__init__.py": ARCHS_FIXTURE,
             "tests/test_config_zoo.py": ZOO_FIXTURE.replace(
                 '["alpha_1b"]', '["alpha_1b", "beta_2b"]')}
    assert rule_ids(tmp_path, files, rules=["config-zoo-coverage"]) == []


# ---------------------------------------------------------------- rule 10

OUTCOME_BAD = """
    class Engine:
        def drop_row(self, seq):
            del self.scheduler.active[seq.slot]
            self.scheduler.tables.release(seq.slot)
            return True

        def sweep(self):
            return self.scheduler.evict_finished()
"""

OUTCOME_CLEAN = """
    class Engine:
        def _terminate(self, seq, outcome):
            del self.scheduler.active[seq.slot]
            self.scheduler.tables.release(seq.slot)
            self._record_outcome(seq.request.rid, outcome, seq.generated)

        def sweep(self):
            done = self.scheduler.evict_finished()
            for seq in done:
                self._record_outcome(seq.request.rid, Outcome.COMPLETED,
                                     seq.generated)
            return done
"""


def test_outcome_rule_flags_unrecorded_removal(tmp_path):
    fs = run(make_tree(tmp_path,
                       {"src/repro/serving/engine.py": OUTCOME_BAD}),
             rules=["engine-outcome-taxonomy"])
    assert [f.rule for f in fs] == ["engine-outcome-taxonomy"] * 2
    assert "drop_row" in fs[0].message and "sweep" in fs[1].message


def test_outcome_rule_clean_when_recorded(tmp_path):
    ids = rule_ids(tmp_path,
                   {"src/repro/serving/engine.py": OUTCOME_CLEAN},
                   rules=["engine-outcome-taxonomy"])
    assert ids == []


def test_outcome_rule_ignores_other_files(tmp_path):
    # scheduler.py's own release/evict calls are the engine's *mechanism*,
    # not its outcome bookkeeping — the rule scopes to engine.py only
    ids = rule_ids(tmp_path,
                   {"src/repro/serving/scheduler.py": OUTCOME_BAD},
                   rules=["engine-outcome-taxonomy"])
    assert ids == []


# ------------------------------------------------------- suppressions

SUPPRESSED = """
    import jax.numpy as jnp

    def kern(s, m):
        # sparklint: disable=no-inline-softmax-fold -- fixture: intentionally inline
        p = jnp.exp(s - m)
        q = jnp.exp(s - m)  # sparklint: disable=no-inline-softmax-fold -- same-line form
        return p + q
"""

UNJUSTIFIED = """
    import jax.numpy as jnp

    def kern(s, m):
        return jnp.exp(s - m)  # sparklint: disable=no-inline-softmax-fold
"""

WRONG_RULE = """
    import jax.numpy as jnp

    def kern(s, m):
        return jnp.exp(s - m)  # sparklint: disable=fsdp-profile-gate -- wrong id
"""


def test_suppression_silences_both_placements(tmp_path):
    ids = rule_ids(tmp_path, {"src/repro/kernels/k.py": SUPPRESSED},
                   rules=["no-inline-softmax-fold"])
    assert ids == []


def test_unjustified_suppression_is_a_finding(tmp_path):
    fs = run(make_tree(tmp_path, {"src/repro/kernels/k.py": UNJUSTIFIED}),
             rules=["no-inline-softmax-fold"])
    assert [f.rule for f in fs] == ["suppression-justification"]


def test_suppression_for_other_rule_does_not_apply(tmp_path):
    ids = rule_ids(tmp_path, {"src/repro/kernels/k.py": WRONG_RULE},
                   rules=["no-inline-softmax-fold"])
    assert ids == ["no-inline-softmax-fold"]


# ------------------------------------------------------- CLI / output

def test_json_output_schema(tmp_path, capsys):
    root = make_tree(tmp_path, {"src/repro/configs/x.py": FSDP_BAD})
    status = main(["--json", "--rule", "fsdp-profile-gate", str(root)])
    out = json.loads(capsys.readouterr().out)
    assert status == 1
    assert out["count"] == 1
    (f,) = out["findings"]
    assert set(f) == {"rule", "path", "line", "message"}
    assert f["rule"] == "fsdp-profile-gate"
    assert f["path"] == "src/repro/configs/x.py"
    assert isinstance(f["line"], int) and f["line"] > 0


def test_cli_exit_codes(tmp_path, capsys):
    clean = make_tree(tmp_path, {"src/repro/kernels/k.py": FOLD_CLEAN})
    assert main([str(clean)]) == 0
    assert main(["--rule", "no-such-rule", str(clean)]) == 2
    assert "ok (0 finding(s)" in capsys.readouterr().out


def test_unparsable_file_is_reported(tmp_path):
    fs = run(make_tree(tmp_path,
                       {"src/repro/kernels/k.py": "def broken(:\n"}),
             rules=["no-inline-softmax-fold"])
    assert fs and "unparsable" in fs[0].message


# ------------------------------------------------------- the real tree

def test_real_tree_is_clean():
    """The merged repo lints clean — the acceptance gate CI enforces."""
    assert run(REPO) == []

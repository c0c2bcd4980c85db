"""Tier-1 gate for the static-analysis subsystem (ISSUE 3).

Asserts three things so regressions fail fast:
  1. the shipped package IS clean: every registered model/preprocessor
     pairing passes the spec-flow checker and the whole package passes
     the custom lints;
  2. each pass actually CATCHES its violation class: a broken
     preprocessor out-spec, a broken decode-ROI declaration, a broken
     abstract execution, undeclared env reads, numpy-in-jit, shm
     discipline breaks — all seeded here and asserted caught;
  3. the flag registry parses/validates like the readers it replaced
     (same accepted spellings, errors naming the flag).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from tensor2robot_tpu import flags
from tensor2robot_tpu.analysis.diagnostics import Diagnostic, format_diagnostics
from tensor2robot_tpu.analysis.lints import (
    DEFAULT_LINT_ROOTS,
    lint_paths,
    lint_source,
)
from tensor2robot_tpu.analysis.specflow import check_model
from tensor2robot_tpu.analysis.targets import default_targets

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- 1. the package is clean --------------------------------------------------


class TestPackageClean:
    def test_lints_clean_over_package(self):
        diagnostics = lint_paths(DEFAULT_LINT_ROOTS, root=_REPO)
        assert not diagnostics, "\n" + format_diagnostics(
            diagnostics, root=_REPO
        )

    def test_specflow_mock_and_transformer_clean(self):
        from tensor2robot_tpu.models.transformer_models import (
            TransformerBCModel,
        )
        from tensor2robot_tpu.utils.mocks import MockT2RModel

        assert check_model(MockT2RModel(), "mock") == []
        model = TransformerBCModel(
            action_size=2,
            pose_size=4,
            episode_length=4,
            image_size=(16, 16),
            use_flash=False,
            device_type="cpu",
        )
        diags = check_model(model, "transformer-bc")
        assert diags == [], "\n" + format_diagnostics(diags)

    def test_specflow_qtopt_clean(self):
        """The QT-Opt pairing at its real geometry (472x472 from a
        512x640 jpeg source with the decode-ROI dual-shape contract) —
        eval_shape only traces, so this stays seconds, not minutes."""
        from tensor2robot_tpu.research.qtopt.t2r_models import (
            Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom,
        )

        model = Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom(
            device_type="cpu"
        )
        diags = check_model(model, "qtopt")
        assert diags == [], "\n" + format_diagnostics(diags)

    def test_all_registered_targets_constructible(self):
        names = [t.name for t in default_targets()]
        assert "qtopt-grasping44" in names
        assert "transformer-bc" in names


# -- 2. seeded violations are caught ------------------------------------------


def _qtopt_model(preprocessor_cls):
    from tensor2robot_tpu.research.qtopt.t2r_models import (
        Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom,
    )

    return Grasping44E2EOpenCloseTerminateGripperStatusHeightToBottom(
        device_type="cpu", preprocessor_cls=preprocessor_cls
    )


class TestSpecflowCatches:
    def test_broken_out_spec(self):
        from tensor2robot_tpu.research.qtopt.t2r_models import (
            DefaultGrasping44ImagePreprocessor,
        )

        class BrokenOutSpec(DefaultGrasping44ImagePreprocessor):
            def get_out_feature_specification(self, mode):
                spec = super().get_out_feature_specification(mode)
                self.update_spec(spec, "state/image", shape=(100, 100, 3))
                return spec

        diags = check_model(_qtopt_model(BrokenOutSpec), "broken")
        assert diags, "broken out-spec must produce diagnostics"
        assert any(d.rule == "specflow-contract" for d in diags)
        text = format_diagnostics(diags)
        assert "state/image" in text and "(100, 100, 3)" in text
        # Anchored at THIS file (the class that declared the contract).
        assert any(
            os.path.basename(d.path) == os.path.basename(__file__)
            and d.line > 0
            for d in diags
        )

    def test_broken_decode_roi(self):
        from tensor2robot_tpu.research.qtopt.t2r_models import (
            DefaultGrasping44ImagePreprocessor,
        )

        class BrokenROI(DefaultGrasping44ImagePreprocessor):
            def get_decode_rois(self, mode):
                from tensor2robot_tpu.data.roi import DecodeROI

                return {"state/image": DecodeROI(9999, 9999, mode="center")}

        diags = check_model(_qtopt_model(BrokenROI), "broken-roi")
        assert any(d.rule == "specflow-roi" for d in diags)
        assert "exceeds source" in format_diagnostics(diags)

    def test_broken_preprocess_fn_shape(self):
        """An out-spec-violating _preprocess_fn is caught by abstract
        execution (the runtime validators run under eval_shape)."""
        from tensor2robot_tpu.research.qtopt.t2r_models import (
            DefaultGrasping44ImagePreprocessor,
        )

        class BrokenTransform(DefaultGrasping44ImagePreprocessor):
            def _preprocess_fn(self, features, labels, mode, rng):
                features, labels = super()._preprocess_fn(
                    features, labels, mode, rng
                )
                features.state.image = features.state.image[:, :10, :10, :]
                return features, labels

        diags = check_model(
            _qtopt_model(BrokenTransform), "broken-fn", modes=("train",)
        )
        assert any(d.rule == "specflow-preprocess" for d in diags)

    def test_missing_model_key(self):
        from tensor2robot_tpu.preprocessors.abstract_preprocessor import (
            NoOpPreprocessor,
        )

        class DropsImage(NoOpPreprocessor):
            def get_out_feature_specification(self, mode):
                spec = self._model.get_feature_specification(mode).copy()
                del spec["state/image"]
                return spec

        diags = check_model(_qtopt_model(DropsImage), "drops-key")
        assert any(
            d.rule == "specflow-contract" and "does not produce" in d.message
            for d in diags
        )


class TestLintsCatch:
    def _rules(self, source):
        return {d.rule for d in lint_source(source, "seeded.py")}

    def test_undeclared_env_read(self):
        rules = self._rules(
            "import os\nx = os.environ.get('T2R_PARSE_FAST', '1')\n"
        )
        assert "env-undeclared" in rules

    def test_undeclared_env_subscript_and_write(self):
        rules = self._rules(
            "import os\n"
            "y = os.environ['T2R_DECODE_ROI']\n"
            "os.environ['T2R_BRAND_NEW'] = '1'\n"
        )
        assert "env-undeclared" in rules

    def test_inconsistent_default(self):
        diags = lint_source(
            "import os\nx = os.environ.get('T2R_PARSE_FAST', '0')\n",
            "seeded.py",
        )
        assert any(d.rule == "env-inconsistent-default" for d in diags)

    def test_unknown_flag_through_registry(self):
        rules = self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_bool('T2R_DOES_NOT_EXIST')\n"
        )
        assert "env-unknown-flag" in rules

    def test_getter_kind_mismatch(self):
        rules = self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_int('T2R_PARSE_BACKEND')\n"
        )
        assert "env-kind-mismatch" in rules

    def test_serve_quant_flags_covered_by_registry_lint(self):
        """The round-11 flags ride the same rails: raw environ reads are
        env-undeclared, wrong-kind getter reads are env-kind-mismatch,
        and the declared getter spellings are clean."""
        for name in ("T2R_SERVE_QUANT", "T2R_SERVE_NATIVE_LAYERS"):
            assert "env-undeclared" in self._rules(
                f"import os\nx = os.environ.get({name!r})\n"
            )
            assert "env-kind-mismatch" in self._rules(
                "from tensor2robot_tpu import flags\n"
                f"x = flags.get_bool({name!r})\n"
            )
        clean = self._rules(
            "from tensor2robot_tpu import flags\n"
            "a = flags.get_enum('T2R_SERVE_QUANT')\n"
            "b = flags.get_str('T2R_SERVE_NATIVE_LAYERS')\n"
        )
        assert "env-kind-mismatch" not in clean
        assert "env-unknown-flag" not in clean

    def test_lowprec_flags_covered_by_registry_lint(self):
        """The round-16 low-precision-compute gates ride the same rails:
        the new eligibility-override flag is declared (raw reads are
        env-undeclared, wrong-kind reads are env-kind-mismatch, the
        declared spelling is clean), and the fp8 regime values are
        registered choices of the two quant selectors."""
        assert "env-undeclared" in self._rules(
            "import os\nx = os.environ.get('T2R_SERVE_NATIVE_LAYERS')\n"
        )
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_bool('T2R_SERVE_NATIVE_LAYERS')\n"
        )
        clean = self._rules(
            "from tensor2robot_tpu import flags\n"
            "a = flags.get_str('T2R_SERVE_NATIVE_LAYERS')\n"
            "b = flags.get_enum('T2R_SERVE_QUANT')\n"
            "c = flags.get_enum('T2R_COLLECTIVE_QUANT')\n"
        )
        assert "env-kind-mismatch" not in clean
        assert "env-unknown-flag" not in clean
        for flag_name in ("T2R_SERVE_QUANT", "T2R_COLLECTIVE_QUANT"):
            choices = flags.get_flag(flag_name).choices
            assert "fp8_e4m3" in choices and "fp8_e5m2" in choices

    def test_lowprec_static_flags_covered_by_registry_lint(self):
        """The round-18 static-calibration gates ride the same rails:
        T2R_SERVE_CALIB is a declared enum (static|dynamic, default
        static) and T2R_SERVE_NATIVE_ATTN a declared str; raw reads are
        env-undeclared, wrong-kind reads env-kind-mismatch, declared
        spellings clean."""
        assert "env-undeclared" in self._rules(
            "import os\nx = os.environ.get('T2R_SERVE_CALIB')\n"
        )
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_bool('T2R_SERVE_CALIB')\n"
            "y = flags.get_int('T2R_SERVE_NATIVE_ATTN')\n"
        )
        clean = self._rules(
            "from tensor2robot_tpu import flags\n"
            "a = flags.get_enum('T2R_SERVE_CALIB')\n"
            "b = flags.get_str('T2R_SERVE_NATIVE_ATTN')\n"
        )
        assert "env-kind-mismatch" not in clean
        assert "env-unknown-flag" not in clean
        spec = flags.get_flag("T2R_SERVE_CALIB")
        assert spec.choices == ("static", "dynamic")
        assert spec.default == "static"

    def test_wire_flags_covered_by_registry_lint(self):
        """The round-22 wire-codec gates ride the same rails: raw
        environ reads are env-undeclared, wrong-kind getter reads are
        env-kind-mismatch, the declared enum spellings are clean, and
        the choice sets pin codec + quant-mode spellings."""
        for name in ("T2R_WIRE", "T2R_WIRE_QUANT"):
            assert "env-undeclared" in self._rules(
                f"import os\nx = os.environ.get({name!r})\n"
            )
            assert "env-kind-mismatch" in self._rules(
                "from tensor2robot_tpu import flags\n"
                f"x = flags.get_int({name!r})\n"
            )
        clean = self._rules(
            "from tensor2robot_tpu import flags\n"
            "a = flags.get_enum('T2R_WIRE')\n"
            "b = flags.get_enum('T2R_WIRE_QUANT')\n"
        )
        assert "env-kind-mismatch" not in clean
        assert "env-unknown-flag" not in clean
        wire = flags.get_flag("T2R_WIRE")
        assert wire.choices == ("pickle", "spec")
        assert wire.default == "pickle"
        quant = flags.get_flag("T2R_WIRE_QUANT")
        assert quant.default == "none"
        for mode in ("fp16", "int8", "fp8_e4m3", "fp8_e5m2"):
            assert mode in quant.choices

    def test_plan_search_flags_covered_by_registry_lint(self):
        """The round-19 measured-search gates ride the same rails: the
        cache-dir/measure-mode strings and the step-count int are
        declared (raw reads env-undeclared, wrong-kind reads
        env-kind-mismatch, declared spellings clean)."""
        for name in (
            "T2R_PLAN_CACHE_DIR", "T2R_PLAN_MEASURE",
            "T2R_PLAN_MEASURE_STEPS",
        ):
            assert "env-undeclared" in self._rules(
                f"import os\nx = os.environ.get({name!r})\n"
            ), name
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_bool('T2R_PLAN_CACHE_DIR')\n"
        )
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_str('T2R_PLAN_MEASURE_STEPS')\n"
        )
        clean = self._rules(
            "from tensor2robot_tpu import flags\n"
            "a = flags.get_str('T2R_PLAN_CACHE_DIR')\n"
            "b = flags.get_str('T2R_PLAN_MEASURE')\n"
            "c = flags.get_int('T2R_PLAN_MEASURE_STEPS')\n"
        )
        assert "env-kind-mismatch" not in clean
        assert "env-unknown-flag" not in clean
        assert flags.get_flag("T2R_PLAN_MEASURE").default == "off"
        assert flags.get_flag("T2R_PLAN_MEASURE_STEPS").minimum == 1

    def test_fabric_flags_covered_by_registry_lint(self):
        """The round-21 cross-host fabric gates ride the same rails:
        the transport selector is a declared enum (local|socket,
        default local — the tier-1 byte-compat pin), the hedge/connect
        timings declared ints; raw reads env-undeclared, wrong-kind
        reads env-kind-mismatch, declared spellings clean."""
        for name in (
            "T2R_FLEET_TRANSPORT", "T2R_FABRIC_HEDGE_MS",
            "T2R_FABRIC_CONNECT_TIMEOUT_MS",
        ):
            assert "env-undeclared" in self._rules(
                f"import os\nx = os.environ.get({name!r})\n"
            ), name
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_bool('T2R_FLEET_TRANSPORT')\n"
            "y = flags.get_str('T2R_FABRIC_HEDGE_MS')\n"
        )
        clean = self._rules(
            "from tensor2robot_tpu import flags\n"
            "a = flags.get_enum('T2R_FLEET_TRANSPORT')\n"
            "b = flags.get_int('T2R_FABRIC_HEDGE_MS')\n"
            "c = flags.get_int('T2R_FABRIC_CONNECT_TIMEOUT_MS')\n"
        )
        assert "env-kind-mismatch" not in clean
        assert "env-unknown-flag" not in clean
        spec = flags.get_flag("T2R_FLEET_TRANSPORT")
        assert spec.choices == ("local", "socket")
        assert spec.default == "local"
        assert flags.get_flag("T2R_FABRIC_CONNECT_TIMEOUT_MS").minimum == 1

    def test_replay_flags_covered_by_registry_lint(self):
        """The round-12 T2R_REPLAY_* + T2R_PARSE_ON_ERROR flags ride the
        same rails: raw environ reads are env-undeclared, wrong-kind
        getter reads are env-kind-mismatch, declared spellings clean."""
        for name in (
            "T2R_REPLAY_SEAL_EPISODES", "T2R_REPLAY_SEAL_BYTES",
            "T2R_REPLAY_SAMPLER", "T2R_REPLAY_RETRIES",
            "T2R_PARSE_ON_ERROR",
        ):
            assert "env-undeclared" in self._rules(
                f"import os\nx = os.environ.get({name!r})\n"
            ), name
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_bool('T2R_REPLAY_SAMPLER')\n"
        )
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_str('T2R_REPLAY_RETRIES')\n"
        )
        clean = self._rules(
            "from tensor2robot_tpu import flags\n"
            "a = flags.get_int('T2R_REPLAY_SEAL_EPISODES')\n"
            "b = flags.get_int('T2R_REPLAY_SEAL_BYTES')\n"
            "c = flags.get_enum('T2R_REPLAY_SAMPLER')\n"
            "d = flags.get_int('T2R_REPLAY_RETRIES')\n"
            "e = flags.get_enum('T2R_PARSE_ON_ERROR')\n"
        )
        assert "env-kind-mismatch" not in clean
        assert "env-unknown-flag" not in clean
        assert "env-undeclared" not in clean

    def test_replay_shard_flags_covered_by_registry_lint(self):
        """The round-13 sharded-fabric flags (T2R_REPLAY_SHARDS /
        T2R_REPLAY_TRANSPORT / T2R_REPLAY_SPILL_BYTES) ride the same
        rails: raw environ reads are env-undeclared, wrong-kind getter
        reads are env-kind-mismatch, declared spellings clean."""
        for name in (
            "T2R_REPLAY_SHARDS", "T2R_REPLAY_TRANSPORT",
            "T2R_REPLAY_SPILL_BYTES",
        ):
            assert "env-undeclared" in self._rules(
                f"import os\nx = os.environ.get({name!r})\n"
            ), name
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_int('T2R_REPLAY_TRANSPORT')\n"
        )
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_enum('T2R_REPLAY_SHARDS')\n"
        )
        clean = self._rules(
            "from tensor2robot_tpu import flags\n"
            "a = flags.get_int('T2R_REPLAY_SHARDS')\n"
            "b = flags.get_enum('T2R_REPLAY_TRANSPORT')\n"
            "c = flags.get_int('T2R_REPLAY_SPILL_BYTES')\n"
        )
        assert "env-kind-mismatch" not in clean
        assert "env-unknown-flag" not in clean
        assert "env-undeclared" not in clean

    def test_plan_flags_covered_by_registry_lint(self):
        """The round-17 sharding-planner gates (T2R_PLAN /
        T2R_PLAN_MEM_BUDGET) ride the same rails: raw environ reads are
        env-undeclared, wrong-kind getter reads are env-kind-mismatch,
        declared spellings clean."""
        for name in ("T2R_PLAN", "T2R_PLAN_MEM_BUDGET"):
            assert "env-undeclared" in self._rules(
                f"import os\nx = os.environ.get({name!r})\n"
            ), name
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_int('T2R_PLAN')\n"
        )
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_str('T2R_PLAN_MEM_BUDGET')\n"
        )
        clean = self._rules(
            "from tensor2robot_tpu import flags\n"
            "a = flags.get_str('T2R_PLAN')\n"
            "b = flags.get_int('T2R_PLAN_MEM_BUDGET')\n"
        )
        assert "env-kind-mismatch" not in clean
        assert "env-unknown-flag" not in clean
        assert "env-undeclared" not in clean

    def test_gate_flags_covered_by_registry_lint(self):
        """The round-14 multi-tenant gateway flags (T2R_GATE_*) ride the
        same rails: raw environ reads are env-undeclared, wrong-kind
        getter reads are env-kind-mismatch, declared spellings clean."""
        for name in (
            "T2R_GATE_QUOTA_RPS", "T2R_GATE_BURST", "T2R_GATE_MAX_QUEUE",
            "T2R_GATE_COALESCE", "T2R_GATE_DEADLINE_MS",
            "T2R_GATE_CIRCUIT_THRESHOLD", "T2R_GATE_CIRCUIT_COOLOFF_MS",
        ):
            assert "env-undeclared" in self._rules(
                f"import os\nx = os.environ.get({name!r})\n"
            ), name
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_bool('T2R_GATE_QUOTA_RPS')\n"
        )
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_int('T2R_GATE_COALESCE')\n"
        )
        clean = self._rules(
            "from tensor2robot_tpu import flags\n"
            "a = flags.get_int('T2R_GATE_QUOTA_RPS')\n"
            "b = flags.get_int('T2R_GATE_BURST')\n"
            "c = flags.get_int('T2R_GATE_MAX_QUEUE')\n"
            "d = flags.get_bool('T2R_GATE_COALESCE')\n"
            "e = flags.get_int('T2R_GATE_DEADLINE_MS')\n"
            "f = flags.get_int('T2R_GATE_CIRCUIT_THRESHOLD')\n"
            "g = flags.get_int('T2R_GATE_CIRCUIT_COOLOFF_MS')\n"
        )
        assert "env-kind-mismatch" not in clean
        assert "env-unknown-flag" not in clean
        assert "env-undeclared" not in clean

    def test_aot_flags_covered_by_registry_lint(self):
        """The round-15 AOT-executable flags (T2R_SERVE_AOT /
        T2R_AOT_EXPORT / T2R_AOT_REQUIRE) ride the same rails: raw
        environ reads are env-undeclared, wrong-kind getter reads are
        env-kind-mismatch, declared spellings clean."""
        for name in ("T2R_SERVE_AOT", "T2R_AOT_EXPORT", "T2R_AOT_REQUIRE"):
            assert "env-undeclared" in self._rules(
                f"import os\nx = os.environ.get({name!r})\n"
            ), name
            assert "env-kind-mismatch" in self._rules(
                "from tensor2robot_tpu import flags\n"
                f"x = flags.get_int({name!r})\n"
            ), name
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_str('T2R_SERVE_AOT')\n"
        )
        clean = self._rules(
            "from tensor2robot_tpu import flags\n"
            "a = flags.get_bool('T2R_SERVE_AOT')\n"
            "b = flags.get_bool('T2R_AOT_EXPORT')\n"
            "c = flags.get_bool('T2R_AOT_REQUIRE')\n"
        )
        assert "env-kind-mismatch" not in clean
        assert "env-unknown-flag" not in clean
        assert "env-undeclared" not in clean

    def test_policy_flags_covered_by_registry_lint(self):
        """The round-20 multi-policy flags (T2R_POLICY_*: artifact-store
        delta codec + replica residency) ride the same rails: raw
        environ reads are env-undeclared, wrong-kind getter reads are
        env-kind-mismatch, declared spellings clean — and the delta
        regime enum registers every collective-codec wire format."""
        for name in (
            "T2R_POLICY_COLD_LOAD", "T2R_POLICY_DELTA_BLOCK",
            "T2R_POLICY_DELTA_QUANT", "T2R_POLICY_DELTA_TOL",
            "T2R_POLICY_MAX_RESIDENT", "T2R_POLICY_MEM_BUDGET",
        ):
            assert "env-undeclared" in self._rules(
                f"import os\nx = os.environ.get({name!r})\n"
            ), name
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_bool('T2R_POLICY_DELTA_BLOCK')\n"
        )
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_int('T2R_POLICY_DELTA_QUANT')\n"
        )
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_int('T2R_POLICY_COLD_LOAD')\n"
        )
        clean = self._rules(
            "from tensor2robot_tpu import flags\n"
            "a = flags.get_bool('T2R_POLICY_COLD_LOAD')\n"
            "b = flags.get_int('T2R_POLICY_DELTA_BLOCK')\n"
            "c = flags.get_enum('T2R_POLICY_DELTA_QUANT')\n"
            "d = flags.get_str('T2R_POLICY_DELTA_TOL')\n"
            "e = flags.get_int('T2R_POLICY_MAX_RESIDENT')\n"
            "f = flags.get_int('T2R_POLICY_MEM_BUDGET')\n"
        )
        assert "env-kind-mismatch" not in clean
        assert "env-unknown-flag" not in clean
        assert "env-undeclared" not in clean
        choices = flags.get_flag("T2R_POLICY_DELTA_QUANT").choices
        for regime in ("none", "fp16", "int8", "fp8_e4m3", "fp8_e5m2"):
            assert regime in choices, regime

    def test_lock_sanitizer_flags_covered_by_registry_lint(self):
        """The lock-sanitizer flags (testing/locksmith.py) ride the
        same rails: raw environ reads are env-undeclared, wrong-kind
        getter reads are env-kind-mismatch, declared spellings clean."""
        for name in ("T2R_LOCK_SANITIZER", "T2R_LOCK_HOLD_BUDGET_MS"):
            assert "env-undeclared" in self._rules(
                f"import os\nx = os.environ.get({name!r})\n"
            ), name
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_int('T2R_LOCK_SANITIZER')\n"
        )
        assert "env-kind-mismatch" in self._rules(
            "from tensor2robot_tpu import flags\n"
            "x = flags.get_bool('T2R_LOCK_HOLD_BUDGET_MS')\n"
        )
        clean = self._rules(
            "from tensor2robot_tpu import flags\n"
            "a = flags.get_bool('T2R_LOCK_SANITIZER')\n"
            "b = flags.get_int('T2R_LOCK_HOLD_BUDGET_MS')\n"
        )
        assert "env-kind-mismatch" not in clean
        assert "env-unknown-flag" not in clean
        assert "env-undeclared" not in clean
        assert flags.get_flag("T2R_LOCK_HOLD_BUDGET_MS").minimum == 0

    def _sleep_rules(self, source, path="tensor2robot_tpu/serving/x.py"):
        return {d.rule for d in lint_source(source, path)}

    def test_bare_sleep_retry_loop_flagged_in_serving_and_replay(self):
        source = (
            "import time\n"
            "def wait_ready(self):\n"
            "    while True:\n"
            "        time.sleep(0.05)\n"
        )
        for path in (
            "tensor2robot_tpu/serving/x.py",
            "tensor2robot_tpu/replay/y.py",
        ):
            assert "sleep-retry-outside-backoff" in self._sleep_rules(
                source, path
            ), path
        # `from time import sleep` is the same hand-rolled cadence.
        assert "sleep-retry-outside-backoff" in self._sleep_rules(
            "from time import sleep\n"
            "def poll(self):\n"
            "    for _ in range(9):\n"
            "        sleep(0.1)\n"
        )

    def test_poll_loop_decorator_allowlists_fixed_interval_monitor(self):
        assert "sleep-retry-outside-backoff" not in self._sleep_rules(
            "import time\n"
            "from tensor2robot_tpu.utils.backoff import poll_loop\n"
            "@poll_loop\n"
            "def _monitor_loop(self):\n"
            "    while True:\n"
            "        time.sleep(0.05)\n"
        )

    def test_computed_delay_and_outside_scope_sleep_clean(self):
        # A schedule-driven delay (backoff.delay_s) is the sanctioned
        # spelling; a literal sleep OUTSIDE a loop is not a poll; and
        # the rule is scoped to serving/ + replay/ only.
        clean = (
            "import time\n"
            "def retry(self, backoff, attempt):\n"
            "    while True:\n"
            "        time.sleep(backoff.delay_s(attempt))\n"
            "def one_shot(self):\n"
            "    time.sleep(0.5)\n"
        )
        assert "sleep-retry-outside-backoff" not in self._sleep_rules(clean)
        looped = (
            "import time\n"
            "def wait(self):\n"
            "    while True:\n"
            "        time.sleep(0.05)\n"
        )
        assert "sleep-retry-outside-backoff" not in self._sleep_rules(
            looped, "tensor2robot_tpu/train/x.py"
        )

    def test_nested_def_inside_loop_not_a_poll(self):
        """A sleep inside a function merely DEFINED within a loop runs
        once per call, not per iteration — out of scope."""
        assert "sleep-retry-outside-backoff" not in self._sleep_rules(
            "import time\n"
            "def outer(self):\n"
            "    while True:\n"
            "        def once():\n"
            "            time.sleep(0.2)\n"
            "        once()\n"
            "        break\n"
        )

    def test_shipped_serving_and_replay_sleep_clean(self):
        """The sweep landed: the live serving/ and replay/ trees carry
        no bare constant-interval sleep loops outside @poll_loop."""
        from tensor2robot_tpu.analysis.lints import lint_paths

        diagnostics = [
            d
            for d in lint_paths(
                ["tensor2robot_tpu/serving", "tensor2robot_tpu/replay"],
                root=_REPO,
            )
            if d.rule == "sleep-retry-outside-backoff"
        ]
        assert diagnostics == []

    def test_numpy_in_jit_decorated(self):
        rules = self._rules(
            "import jax\nimport numpy as np\n"
            "@jax.jit\ndef f(x):\n    return np.asarray(x) + 1\n"
        )
        assert "jit-host-numpy" in rules

    def test_numpy_in_jit_wrapped(self):
        rules = self._rules(
            "import jax\nimport numpy as np\n"
            "def step(x):\n    return np.zeros(3) + x\n"
            "run = jax.jit(step)\n"
        )
        assert "jit-host-numpy" in rules

    def test_numpy_shape_arithmetic_allowed(self):
        rules = self._rules(
            "import jax\nimport numpy as np\n"
            "@jax.jit\ndef f(x):\n"
            "    n = np.prod(x.shape)\n"
            "    return x.reshape(n).astype(np.float32)\n"
        )
        assert "jit-host-numpy" not in rules

    def test_shm_discipline(self):
        source = (
            "from multiprocessing import shared_memory\n"
            "def worker(free_queue):\n"
            "    name = free_queue.get()\n"
            "    shm = shared_memory.SharedMemory(create=True, size=8)\n"
            "    shm.unlink()\n"
        )
        rules = self._rules(source)
        assert {
            "shm-blocking-get",
            "shm-create-outside-ring",
            "shm-unlink-outside-ring",
        } <= rules

    def test_shm_blocking_put_in_release(self):
        source = (
            "class _MyShmRing:\n"
            "    def release(self, name):\n"
            "        self.free_queue.put(name)\n"
        )
        rules = self._rules(source)
        assert "shm-blocking-put-in-release" in rules

    def test_ring_owner_is_allowed(self):
        source = (
            "from multiprocessing import shared_memory\n"
            "class _ShmBatchRing:\n"
            "    def __init__(self):\n"
            "        self.shm = shared_memory.SharedMemory(create=True, size=8)\n"
            "    def close(self):\n"
            "        self.shm.unlink()\n"
            "    def release(self, name):\n"
            "        self.free_queue.put_nowait(name)\n"
        )
        assert lint_source(source, "ring.py") == []

    def test_syntax_error_is_a_diagnostic(self):
        diags = lint_source("def broken(:\n", "bad.py")
        assert [d.rule for d in diags] == ["syntax-error"]

    # -- exception discipline -------------------------------------------------

    _SERVING_PATH = "tensor2robot_tpu/serving/seeded.py"

    def test_bare_except_flagged_even_with_real_body(self):
        source = (
            "def f():\n"
            "    try:\n        work()\n"
            "    except:\n        log()\n"
        )
        diags = lint_source(source, self._SERVING_PATH)
        assert any(d.rule == "swallowed-exception" for d in diags)

    def test_silent_broad_handler_flagged(self):
        for handler in ("except Exception:", "except BaseException:",
                        "except (ValueError, Exception):"):
            source = (
                "def f():\n"
                "    try:\n        work()\n"
                f"    {handler}\n        pass\n"
            )
            diags = lint_source(source, self._SERVING_PATH)
            assert any(
                d.rule == "swallowed-exception" for d in diags
            ), handler

    def test_handler_that_does_something_is_clean(self):
        for body in ("log()", "x = None", "raise", "return 1"):
            source = (
                "def f():\n"
                "    try:\n        return work()\n"
                f"    except Exception:\n        {body}\n"
            )
            assert lint_source(source, self._SERVING_PATH) == [], body

    def test_specific_exception_pass_is_clean(self):
        source = (
            "def f():\n"
            "    try:\n        work()\n"
            "    except FileNotFoundError:\n        pass\n"
        )
        assert lint_source(source, self._SERVING_PATH) == []

    def test_allowlist_decorator_permits_swallow(self):
        source = (
            "from tensor2robot_tpu.utils.errors import best_effort_cleanup\n"
            "@best_effort_cleanup\n"
            "def reap(q):\n"
            "    try:\n        q.close()\n"
            "    except Exception:\n        pass\n"
        )
        assert lint_source(source, self._SERVING_PATH) == []
        # ... but the decorator does NOT bless a bare except.
        bare = (
            "from tensor2robot_tpu.utils.errors import best_effort_cleanup\n"
            "@best_effort_cleanup\n"
            "def reap(q):\n"
            "    try:\n        q.close()\n"
            "    except:\n        pass\n"
        )
        diags = lint_source(bare, self._SERVING_PATH)
        assert any(d.rule == "swallowed-exception" for d in diags)

    def test_swallow_outside_scope_is_clean(self):
        source = (
            "def f():\n"
            "    try:\n        work()\n"
            "    except Exception:\n        pass\n"
        )
        assert lint_source(source, "tensor2robot_tpu/ops/seeded.py") == []

    def test_swallow_in_train_and_predictors_scoped(self):
        source = (
            "def f():\n"
            "    try:\n        work()\n"
            "    except Exception:\n        pass\n"
        )
        for path in (
            "tensor2robot_tpu/train/seeded.py",
            "tensor2robot_tpu/predictors/seeded.py",
        ):
            diags = lint_source(source, path)
            assert any(
                d.rule == "swallowed-exception" for d in diags
            ), path

    def test_swallow_in_replay_scoped(self):
        """replay/ is failure-handling code top to bottom: the silent-
        swallow ban covers it (positive), with best_effort and specific
        exceptions still clean (negative)."""
        path = "tensor2robot_tpu/replay/seeded.py"
        silent = (
            "def f():\n"
            "    try:\n        work()\n"
            "    except Exception:\n        pass\n"
        )
        diags = lint_source(silent, path)
        assert any(d.rule == "swallowed-exception" for d in diags)
        clean = (
            "from tensor2robot_tpu.utils.errors import best_effort\n"
            "def f(q):\n"
            "    best_effort(q.put, 1)\n"
            "    try:\n        work()\n"
            "    except OSError:\n        pass\n"
        )
        assert lint_source(clean, path) == []

    # -- collective discipline ------------------------------------------------

    _TRAIN_PATH = "tensor2robot_tpu/train/seeded.py"

    def test_raw_lax_collective_in_trainer_flagged(self):
        source = (
            "import jax\nfrom jax import lax\n"
            "def f(x):\n"
            "    return lax.psum(x, 'data') + jax.lax.all_to_all("
            "x, 'data', 0, 0)\n"
        )
        diags = lint_source(source, self._TRAIN_PATH)
        rules = [d.rule for d in diags]
        assert rules.count("collective-outside-registry") == 2

    def test_shard_map_import_in_trainer_flagged(self):
        for stmt in (
            "from jax import shard_map\n",
            "from jax.experimental.shard_map import shard_map\n",
        ):
            diags = lint_source(stmt, self._TRAIN_PATH)
            assert any(
                d.rule == "collective-outside-registry" for d in diags
            ), stmt

    def test_lax_psum_from_import_flagged(self):
        diags = lint_source(
            "from jax.lax import psum\n", self._TRAIN_PATH
        )
        assert any(d.rule == "collective-outside-registry" for d in diags)

    def test_lax_module_alias_flagged(self):
        # Aliasing the module must not walk past the gate.
        for source in (
            "import jax.lax as jl\ndef f(x):\n"
            "    return jl.psum(x, 'data')\n",
            "from jax import lax as jlax\ndef f(x):\n"
            "    return jlax.all_gather(x, 'data')\n",
        ):
            diags = lint_source(source, self._TRAIN_PATH)
            assert any(
                d.rule == "collective-outside-registry" for d in diags
            ), source

    def test_registry_itself_exempt(self):
        source = (
            "from jax import lax\n"
            "from jax.experimental.shard_map import shard_map\n"
            "def f(x):\n    return lax.psum(x, 'data')\n"
        )
        assert (
            lint_source(
                source, "tensor2robot_tpu/parallel/collectives.py"
            )
            == []
        )

    def test_sanctioned_spellings_and_outside_scope_clean(self):
        # collectives.* calls in the trainer are the sanctioned route.
        source = (
            "from tensor2robot_tpu.parallel import collectives\n"
            "def f(x):\n"
            "    return collectives.psum(x, 'data') + "
            "collectives.axis_index('data')\n"
        )
        assert lint_source(source, self._TRAIN_PATH) == []
        # ops/ is out of scope for this rule.
        raw = "from jax import lax\ndef f(x):\n    return lax.psum(x, 'i')\n"
        assert lint_source(raw, "tensor2robot_tpu/ops/seeded.py") == []
        # Zero-byte manual-axis bookkeeping stays legal raw.
        bookkeeping = (
            "from jax import lax\n"
            "def f(x):\n    return lax.axis_index('data'), "
            "lax.pcast(x, ('data',), to='varying')\n"
        )
        assert lint_source(bookkeeping, self._TRAIN_PATH) == []

    # -- sharding discipline --------------------------------------------------

    def test_raw_sharding_construction_in_trainer_flagged(self):
        """NamedSharding/PartitionSpec spelled raw in train/ — including
        the `as P` alias and the fully-qualified jax.sharding path — is
        hand-wired layout drift the planner contract forbids."""
        for source in (
            "from jax.sharding import PartitionSpec\n"
            "def f():\n    return PartitionSpec('data')\n",
            "from jax.sharding import NamedSharding, PartitionSpec\n"
            "def f(mesh):\n"
            "    return NamedSharding(mesh, PartitionSpec())\n",
            "from jax.sharding import PartitionSpec as P\n"
            "def f():\n    return P(None, 'data')\n",
            "import jax\ndef f():\n"
            "    return jax.sharding.PartitionSpec('data')\n",
        ):
            diags = lint_source(source, self._TRAIN_PATH)
            assert any(
                d.rule == "sharding-outside-planner" for d in diags
            ), source

    def test_tensor_parallel_spellings_flagged(self):
        """The round-19 TP widening brings new constructor spellings
        into reach — PositionalSharding and the conventional bare-P
        alias — and the lint covers them in train/ too."""
        for source in (
            "from jax.sharding import PositionalSharding\n"
            "def f(devices):\n    return PositionalSharding(devices)\n",
            "import jax\ndef f(devices):\n"
            "    return jax.sharding.PositionalSharding(devices)\n",
            "from jax.sharding import PartitionSpec as P\n"
            "def f():\n    return P('fsdp')\n",
        ):
            diags = lint_source(source, self._TRAIN_PATH)
            assert any(
                d.rule == "sharding-outside-planner" for d in diags
            ), source

    def test_hand_sharded_decorator_allowlists_site(self):
        source = (
            "from jax.sharding import PartitionSpec\n"
            "from tensor2robot_tpu.parallel.planner import hand_sharded\n"
            "@hand_sharded\n"
            "def f():\n    return PartitionSpec('data')\n"
        )
        assert lint_source(source, self._TRAIN_PATH) == []

    def test_sharding_construction_outside_scope_clean(self):
        # parallel/ is the sanctioned home of spec construction; other
        # packages (export, serving, tests) are out of scope too.
        source = (
            "from jax.sharding import NamedSharding, PartitionSpec\n"
            "def f(mesh):\n"
            "    return NamedSharding(mesh, PartitionSpec('data'))\n"
        )
        for path in (
            "tensor2robot_tpu/parallel/planner.py",
            "tensor2robot_tpu/parallel/mesh.py",
            "tensor2robot_tpu/export/seeded.py",
        ):
            assert lint_source(source, path) == [], path
        # Consuming the helpers in train/ is the sanctioned route.
        clean = (
            "from tensor2robot_tpu.parallel import mesh as mesh_lib\n"
            "def f(mesh, shape):\n"
            "    return (mesh_lib.REPLICATED_SPEC,\n"
            "            mesh_lib.batch_partition_spec(mesh, shape),\n"
            "            mesh_lib.flat_shard_sharding(mesh))\n"
        )
        assert lint_source(clean, self._TRAIN_PATH) == []

    def test_shipped_train_package_sharding_clean(self):
        """The refactor actually landed: no raw constructor survives in
        the shipped train/ package."""
        from tensor2robot_tpu.analysis.lints import lint_paths

        diags = [
            d
            for d in lint_paths(["tensor2robot_tpu/train"], root=_REPO)
            if d.rule == "sharding-outside-planner"
        ]
        assert diags == []


# -- 3. the flag registry -----------------------------------------------------


class TestFlagRegistry:
    def test_every_declared_flag_is_namespaced_and_documented(self):
        for spec in flags.all_flags():
            assert spec.name.startswith("T2R_")
            assert spec.doc and spec.owner

    def test_bool_parse_and_error(self, monkeypatch):
        monkeypatch.delenv("T2R_PARSE_FAST", raising=False)
        assert flags.get_bool("T2R_PARSE_FAST") is True
        monkeypatch.setenv("T2R_PARSE_FAST", "0")
        assert flags.get_bool("T2R_PARSE_FAST") is False
        monkeypatch.setenv("T2R_PARSE_FAST", "yes")
        with pytest.raises(ValueError, match="T2R_PARSE_FAST"):
            flags.get_bool("T2R_PARSE_FAST")

    def test_enum_parse_and_error(self, monkeypatch):
        monkeypatch.setenv("T2R_PARSE_BACKEND", "process")
        assert flags.get_enum("T2R_PARSE_BACKEND") == "process"
        monkeypatch.setenv("T2R_PARSE_BACKEND", "fork")
        with pytest.raises(ValueError, match="T2R_PARSE_BACKEND"):
            flags.get_enum("T2R_PARSE_BACKEND")

    def test_int_clamps_to_minimum(self, monkeypatch):
        monkeypatch.setenv("T2R_DECODE_CACHE_MB", "-5")
        assert flags.get_int("T2R_DECODE_CACHE_MB") == 0
        monkeypatch.setenv("T2R_DECODE_CACHE_MB", "64")
        assert flags.get_int("T2R_DECODE_CACHE_MB") == 64
        monkeypatch.setenv("T2R_DECODE_CACHE_MB", "lots")
        with pytest.raises(ValueError, match="T2R_DECODE_CACHE_MB"):
            flags.get_int("T2R_DECODE_CACHE_MB")

    def test_optional_int_unset_is_none(self, monkeypatch):
        monkeypatch.delenv("T2R_PARSE_WORKERS", raising=False)
        assert flags.get_optional_int("T2R_PARSE_WORKERS") is None
        monkeypatch.setenv("T2R_PARSE_WORKERS", "3")
        assert flags.get_optional_int("T2R_PARSE_WORKERS") == 3

    def test_unknown_flag_rejected(self):
        with pytest.raises(KeyError, match="not a declared T2R flag"):
            flags.get_bool("T2R_NOT_A_FLAG")

    def test_kind_mismatch_rejected(self):
        with pytest.raises(TypeError, match="enum flag"):
            flags.get_bool("T2R_PARSE_BACKEND")

    def test_write_and_restore_roundtrip(self, monkeypatch):
        monkeypatch.delenv("T2R_DECODE_ROI", raising=False)
        saved = flags.read_raw("T2R_DECODE_ROI")
        assert saved is None
        flags.write_env("T2R_DECODE_ROI", False)
        assert flags.get_bool("T2R_DECODE_ROI") is False
        flags.restore_env("T2R_DECODE_ROI", saved)
        assert flags.get_bool("T2R_DECODE_ROI") is True
        with pytest.raises(ValueError, match="T2R_PARSE_BACKEND"):
            flags.write_env("T2R_PARSE_BACKEND", "fork")

    def test_migrated_readers_agree_with_registry(self, monkeypatch):
        """The pre-registry readers' semantics survived the migration:
        same defaults, same accepted spellings (drift fix satellite)."""
        from tensor2robot_tpu.data.dataset import (
            default_decode_roi,
            default_parse_backend,
            default_parse_fast,
            default_parse_shm,
        )
        from tensor2robot_tpu.data.wire import default_decode_cache_mb

        for name in (
            "T2R_DECODE_ROI",
            "T2R_PARSE_BACKEND",
            "T2R_PARSE_FAST",
            "T2R_PARSE_SHM",
            "T2R_DECODE_CACHE_MB",
        ):
            monkeypatch.delenv(name, raising=False)
        assert default_decode_roi() is True
        assert default_parse_backend() == "thread"
        assert default_parse_fast() is True
        assert default_parse_shm() is True
        assert default_decode_cache_mb() == 512


# -- CLI ----------------------------------------------------------------------


class TestCLI:
    def test_lint_only_clean_file_exits_zero(self, tmp_path):
        clean = tmp_path / "clean.py"
        clean.write_text("x = 1\n")
        result = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "t2r_check.py"),
             "--lint-only", str(clean)],
            capture_output=True, text=True, cwd=_REPO,
        )
        assert result.returncode == 0, result.stdout + result.stderr

    def test_lint_only_seeded_violation_exits_one(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(
            "import os\nx = os.environ.get('T2R_PARSE_FAST', '0')\n"
        )
        result = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "t2r_check.py"),
             "--lint-only", str(bad)],
            capture_output=True, text=True, cwd=_REPO,
        )
        assert result.returncode == 1, result.stdout + result.stderr
        assert "env-undeclared" in result.stdout
        assert "env-inconsistent-default" in result.stdout

    def test_flags_listing(self):
        result = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "t2r_check.py"),
             "--flags"],
            capture_output=True, text=True, cwd=_REPO,
        )
        assert result.returncode == 0
        for spec in flags.all_flags():
            assert spec.name in result.stdout

    def test_concurrency_only_shipped_tree_exits_zero(self):
        result = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "t2r_check.py"),
             "--concurrency-only"],
            capture_output=True, text=True, cwd=_REPO,
        )
        assert result.returncode == 0, result.stdout + result.stderr
        assert "[concurrency] clean" in result.stdout

    def test_concurrency_only_seeded_violation_exits_one(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(
            "import threading\n"
            "\n"
            "class Hub:\n"
            "    def __init__(self):\n"
            "        self._a = threading.Lock()\n"
            "        self._b = threading.Lock()\n"
            "\n"
            "    def fwd(self):\n"
            "        with self._a:\n"
            "            with self._b:\n"
            "                pass\n"
            "\n"
            "    def rev(self):\n"
            "        with self._b:\n"
            "            with self._a:\n"
            "                pass\n"
        )
        result = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "t2r_check.py"),
             "--concurrency-only", str(bad)],
            capture_output=True, text=True, cwd=_REPO,
        )
        assert result.returncode == 1, result.stdout + result.stderr
        assert "conc-lock-order-cycle" in result.stdout

    def test_concurrency_only_bad_scope_exits_two(self):
        result = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "t2r_check.py"),
             "--concurrency-only", "/nonexistent/scope"],
            capture_output=True, text=True, cwd=_REPO,
        )
        assert result.returncode == 2, result.stdout + result.stderr

    def test_run_checks_script_exists_and_executable(self):
        script = os.path.join(_REPO, "tools", "run_checks.sh")
        assert os.path.exists(script)
        assert os.access(script, os.X_OK)

    @pytest.mark.slow
    def test_sanitize_pass_end_to_end(self, tmp_path):
        """Builds the ASan/UBSan driver, asserts the OOB canary aborts,
        and survives the full malformed corpus (acceptance: truncated-
        record corpus under the sanitizer build is caught by its pass)."""
        result = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "t2r_check.py"),
             "--skip-specflow", "--skip-lints", "--sanitize",
             "--corpus", str(tmp_path / "corpus")],
            capture_output=True, text=True, cwd=_REPO,
        )
        if "build failed" in result.stdout:
            pytest.skip("no ASan toolchain on this host")
        assert result.returncode == 0, result.stdout + result.stderr
        assert "sanitizer canary OK" in result.stdout
        assert "survived" in result.stdout

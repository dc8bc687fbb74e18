"""Aux-subsystem tests: config tree round-trip + build, metrics logger,
step timer, and the training entry scripts end-to-end (tiny)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from alphafold2_tpu.config import Experiment, ModelConfig
from alphafold2_tpu.utils import MetricsLogger, StepTimer

pytestmark = pytest.mark.quick

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestConfig:
    def test_roundtrip(self):
        exp = Experiment()
        exp.model.dim = 64
        exp.model.reversible = True
        exp.mesh.i = 2
        text = exp.to_json()
        back = Experiment.from_json(text)
        assert back.model.dim == 64
        assert back.model.reversible
        assert back.mesh.i == 2

    def test_build(self):
        exp = Experiment()
        exp.model.dim, exp.model.depth = 32, 1
        exp.model.bfloat16 = False
        model, tx, mesh = exp.build()
        assert model.dim == 32
        assert mesh is None  # 1x1x1
        assert tx is not None

    def test_model_config_matches_model_fields(self):
        import jax
        model = ModelConfig(dim=32, depth=1, bfloat16=False).build()
        seq = jax.random.randint(jax.random.PRNGKey(0), (1, 8), 0, 21)
        params = model.init(jax.random.PRNGKey(1), seq)
        ret = model.apply(params, seq)
        assert ret.distance.shape == (1, 8, 8, 37)


class TestLoggerTimer:
    def test_metrics_logger_jsonl(self, tmp_path):
        path = tmp_path / "m.jsonl"
        with MetricsLogger(str(path), stdout=False) as log:
            log.log(step=0, loss=1.5)
            log.log(step=1, loss=1.25, extra=2)
        lines = path.read_text().strip().splitlines()
        assert len(lines) == 2
        rec = json.loads(lines[1])
        assert rec["step"] == 1 and np.isclose(rec["loss"], 1.25)

    def test_step_timer(self):
        t = StepTimer()
        for _ in range(3):
            with t.measure():
                pass
        s = t.summary()
        assert s["count"] == 3
        assert s["mean_s"] >= 0


@pytest.mark.parametrize("script,extra", [
    ("scripts/train_distogram.py", []),
    ("scripts/train_end2end.py", ["--structure-module", "egnn"]),
])
def test_training_scripts_run(tmp_path, script, extra):
    """The reference's train scripts are stale/broken (SURVEY.md §2.6);
    ours must actually run: 3 tiny steps on synthetic data."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    cfg = {
        "model": {"dim": 32, "depth": 1, "heads": 2, "dim_head": 16,
                  "bfloat16": False},
        "data": {"crop_len": 12, "msa_depth": 2},
        "train": {"num_steps": 3, "log_every": 1,
                  "grad_accum_every": 1},
    }
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(cfg))
    log_path = tmp_path / "metrics.jsonl"
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, script), "--config",
         str(cfg_path), "--log", str(log_path)] + extra,
        capture_output=True, text=True, env=env, cwd=REPO, timeout=900)
    assert result.returncode == 0, result.stderr[-2000:]
    lines = log_path.read_text().strip().splitlines()
    assert len(lines) == 3
    assert "loss" in json.loads(lines[0])

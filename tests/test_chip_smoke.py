"""``chip_smoke.py`` rehearsed on the CPU: its phases at a tiny size with the
Pallas kernels in interpret mode, its refusal of a non-TPU platform, and
the compile-cache path choice it relies on."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import lnn_init
from repro.utils import compile_cache

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def world():
    events, feat_dim = chip_smoke.make_stream(seed=0, users=30)
    config = chip_smoke.service_config(feat_dim)
    params = lnn_init(jax.random.PRNGKey(0), config.to_lnn_config())
    return events, config, params, chip_smoke.replay(config, params, events)


def test_replay_phase_scores_every_order_through_the_kernels(world):
    events, config, _, run = world
    assert config.model.use_pallas and config.engine.num_workers == 4
    assert config.model.hidden_dim == 64 and config.engine.k_max == 8
    assert set(run["scores"]) == {ev.order_id for ev in events}
    assert np.isfinite(list(run["scores"].values())).all()
    assert run["buckets"] and set(run["buckets"]) <= {2, 4, 8, 16}
    assert run["launches"], "no stage-1 refresh ran"
    for pg, h in run["launches"]:
        assert h.shape == (pg.features.shape[0], config.model.hidden_dim)


def test_kernels_native_phase_sees_the_interpreter_on_cpu(world):
    _, config, _, run = world
    out = chip_smoke.kernels_native(run, config)
    assert out == {"stage2_native": False, "stage1_native": False,
                   "stage1_nodes": max(pg.features.shape[0]
                                       for pg, _ in run["launches"])}


def test_reference_phases_hold_their_bounds(world):
    events, config, _, run = world
    s1 = chip_smoke.stage1_reference(run, config)
    assert s1["rows"] == sum(pg.features.shape[0] for pg, _ in run["launches"])
    assert s1["rel"] <= chip_smoke.STAGE1_RTOL
    sc = chip_smoke.score_reference(run, config, events)
    assert sc["orders"] == len(events)
    assert sc["max_abs"] <= chip_smoke.SCORE_ATOL


def test_replay_parity_phase_is_bit_identical_on_cpu(world):
    events, config, params, run = world
    run1 = chip_smoke.replay(config.replace(engine={"num_workers": 1}),
                             params, events)
    assert chip_smoke.replay_parity(run, run1) == {
        "bit_identical": True, "orders_differing": 0, "max_abs": 0.0}


def test_gateway_phase_wire_scores_equal_in_process(world):
    events, config, params, _ = world
    out = chip_smoke.gateway_parity(config, params, events[:12])
    assert out["wire_equal"] and out["orders"] == 12


def test_main_refuses_a_non_tpu_platform_before_any_phase(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main(["--users", "30"])
    assert exc.value.code not in (0, None)
    assert "not a TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_compile_cache_dir_follows_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    monkeypatch.delenv(compile_cache.ENV_VAR)
    assert compile_cache.compile_cache_dir() == os.path.join(ROOT, ".jax_cache")


def test_compiled_programs_land_in_the_environment_dir(tmp_path):
    code = ("from repro.utils.compile_cache import enable_compile_cache\n"
            "import jax, jax.numpy as jnp\n"
            "print(enable_compile_cache())\n"
            "jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()\n")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.path.join(ROOT, "src"),
           compile_cache.ENV_VAR: str(tmp_path)}
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == str(tmp_path)
    assert any(p.is_file() for p in tmp_path.rglob("*"))

"""Carbon traces (Table II calibration), Eq. 1 accounting, workload model."""
import os
import subprocess
import sys

import numpy as np
import pytest
from hypothesis import given, strategies as st

from repro.core.carbon import (REGIONS, SEASONS, carbon_intensity_trace,
                               request_carbon)
from repro.core.energy import (A100_40GB, LLAMA2_7B, LLAMA2_13B, TPU_V5E,
                               EnergyModel, hardware_for)
from repro.core.workload import N_LEVELS, TASKS, Workload


@pytest.mark.parametrize("region", list(REGIONS))
@pytest.mark.parametrize("season", SEASONS)
def test_trace_within_annual_bounds(region, season):
    r = REGIONS[region]
    tr = carbon_intensity_trace(region, season, hours=24 * 28)
    assert tr.min() >= r.ci_min - 1e-9
    assert tr.max() <= r.ci_max + 1e-9
    assert tr.std() > 0.02 * (r.ci_max - r.ci_min)  # actually varies


def test_trace_deterministic():
    a = carbon_intensity_trace("CA", "jun")
    b = carbon_intensity_trace("CA", "jun")
    np.testing.assert_array_equal(a, b)


def test_trace_pinned_values():
    """Regression for the salted-hash seeding bug: traces are seeded from a
    stable digest, so these exact values hold on every machine and under
    every PYTHONHASHSEED. If this fails, the seeding scheme changed and
    every downstream 'deterministic per (region, season)' claim broke."""
    ca = carbon_intensity_trace("CA", "jun")
    np.testing.assert_allclose(
        ca[:3], [153.649541732424, 148.20864970912868, 148.92312928014482],
        rtol=0, atol=1e-9)
    np.testing.assert_allclose(ca[100], 139.7275458948663, rtol=0, atol=1e-9)
    np.testing.assert_allclose(carbon_intensity_trace("TX", "feb")[0],
                               379.1893120650777, rtol=0, atol=1e-9)


def test_trace_identical_across_hash_seeds():
    """Bit-identical across fresh interpreters with different
    PYTHONHASHSEED (the old ``abs(hash((region, season)))`` seeding was
    salted per process)."""
    snippet = ("from repro.core.carbon import carbon_intensity_trace as t;"
               "print(t('CA', 'jun')[:4].tobytes().hex())")
    outs = []
    for seed in ("0", "12345"):
        env = dict(os.environ, PYTHONPATH="src", PYTHONHASHSEED=seed)
        r = subprocess.run([sys.executable, "-c", snippet], env=env,
                           capture_output=True, text=True, timeout=120,
                           cwd=os.path.dirname(os.path.dirname(__file__)))
        assert r.returncode == 0, r.stderr
        outs.append(r.stdout.strip())
    assert outs[0] == outs[1]
    want = carbon_intensity_trace("CA", "jun")[:4].tobytes().hex()
    assert outs[0] == want


def test_request_carbon_eq1():
    # C = CI * E * PUE + embodied/lifetime * t
    c = request_carbon(100.0, 2.0, 10.0, 150_000.0, 1.5e8, pue=1.2)
    assert c == pytest.approx(100 * 2 * 1.2 + 150_000 / 1.5e8 * 10)


def test_energy_model_paper_anchors():
    em = EnergyModel(A100_40GB)
    # Fig 2b: carbon/energy linear in generated tokens
    e100 = em.request_energy_kwh(LLAMA2_13B, 200, 100)
    e200 = em.request_energy_kwh(LLAMA2_13B, 200, 200)
    e400 = em.request_energy_kwh(LLAMA2_13B, 200, 400)
    d1, d2 = e200 - e100, (e400 - e200) / 2
    assert d2 == pytest.approx(d1, rel=0.25)  # near-linear slope
    # Fig 2a: 13B costs ~1.8x 7B per token
    r = em.request_energy_kwh(LLAMA2_13B, 100, 200) / \
        em.request_energy_kwh(LLAMA2_7B, 100, 200)
    assert 1.4 < r < 2.3


@pytest.mark.parametrize("kind,spec", [("TPU v5 lite", TPU_V5E),
                                       ("cpu", A100_40GB)])
def test_hardware_for_known_device_kinds(kind, spec):
    # a CPU run keeps pricing the paper's modelled A100, so CPU digests
    # and baselines do not move
    assert hardware_for(kind) is spec


def test_hardware_for_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no HardwareSpec"):
        hardware_for("TPU v4")


def test_workload_request_structure():
    w = Workload(seed=3)
    r = w.sample_request(5.0)
    assert r.task in TASKS
    assert len(r.gen_tokens) == N_LEVELS
    # directives shorten generation: L0 >= L1 >= L2
    assert r.gen_tokens[0] >= r.gen_tokens[1] >= r.gen_tokens[2]
    assert 0 <= r.preferred < N_LEVELS


def test_mixture_normalized_and_rps_positive():
    w = Workload(seed=0)
    for t in (0.0, 7.5, 13.0, 22.0):
        mix = w.mixture(t)
        assert sum(mix.values()) == pytest.approx(1.0)
        assert w.rps(t) > 0


def test_judge_head_to_head_consistency():
    w = Workload(seed=1)
    rng = np.random.default_rng(0)
    r = w.sample_request(0.0)
    wins = sum(r.judge_prefers(rng, r.preferred, (r.preferred + 1) % 3)
               for _ in range(300))
    assert wins > 250  # judge prefers the preferred level ~97% of the time


@given(st.integers(0, 10_000))
def test_judge_pick_is_valid_level(seed):
    w = Workload(seed=seed % 50)
    rng = np.random.default_rng(seed)
    r = w.sample_request(seed * 0.1)
    assert 0 <= r.judge_pick(rng) < N_LEVELS
    assert r.judge_pick(rng, [1, 2]) in (1, 2)

"""CPU rehearsal of chip_smoke.py: every one-chip phase at a tiny size,
and the entry's refusal to run without a TPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke

ROOT = Path(chip_smoke.__file__).resolve().parent

TINY = chip_smoke.Size(
    space=dict(pe_rows=(8, 12), pe_cols=(8, 14), gbuf_kb=(54.0,),
               spad_ifmap=(12,), spad_filter=(112, 224), spad_psum=(16,),
               pe_type=tuple(range(5)), bandwidth_gbps=(25.6,)),
    models=4, chunk_size=32, sample_points=96, fit_points=120,
    eval_points=150, search_models=2,
    search_space=dict(pe_rows=(8, 12), pe_cols=(8, 14), gbuf_kb=(54.0,),
                      spad_ifmap=(12,), spad_filter=(112, 224),
                      spad_psum=(16,), pe_type=tuple(range(5)),
                      bandwidth_gbps=(25.6,), mapping=(0.0, 5.0, 30.0)),
    search_evals=120, qat_steps=2)


@pytest.fixture(scope="module")
def models():
    return chip_smoke._models(TINY)


@pytest.fixture(scope="module")
def front(models):
    return chip_smoke.phase_joint_sweep(models, TINY)


def test_joint_sweep_claim_holds(front):
    assert front.points_evaluated == 4 * 40
    assert len(front.archive) > 0


def test_walk_contracts(models, front):
    chip_smoke.phase_contract(models, TINY, front)


def test_cpu_reference_phase(models, front):
    chip_smoke.phase_vs_cpu(models, TINY, front)


def test_surrogate_phase(models):
    chip_smoke.phase_surrogate(models, TINY)


def test_frontserver_phase(models):
    chip_smoke.phase_frontserver(models, TINY)


def test_search_phase(models):
    chip_smoke.phase_search(models, TINY)


def test_qat_phase():
    chip_smoke.phase_qat(TINY)


def test_front_mismatch_fails(models, front):
    """The bit-identity check rejects a front that lost a row."""
    from repro.core import coexplore_front
    sub = coexplore_front(models, TINY.space, chunk_size=TINY.chunk_size,
                          max_points=40, seed=1)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.assert_same_front(front, sub, "subsample")


def test_entry_refuses_a_cpu_only_process():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    for line in out.stdout.splitlines():
        if line.startswith("{"):
            assert json.loads(line).get("ok") is not True

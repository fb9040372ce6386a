"""chip_smoke.py off the chip: it refuses to run without a TPU (and outside
a checkout) and prints no result; its phase runner, agreement count and
checks work end to end on tiny models through the CPU serving path."""
import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import load_params, make_prompts
from repro.serving.config import EngineConfig

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load_smoke()


def _run_script(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _printed_result(stdout):
    for line in stdout.splitlines():
        try:
            json.loads(line)
            return True
        except ValueError:
            continue
    return False


def test_refuses_without_a_tpu():
    res = _run_script(ROOT, ROOT / "chip_smoke.py")
    assert res.returncode != 0
    assert "'cpu'" in res.stderr and "no phase was run" in res.stderr
    assert not _printed_result(res.stdout)


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    res = _run_script(tmp_path, tmp_path / "chip_smoke.py")
    assert res.returncode != 0
    assert "no repro package" in res.stderr
    assert not _printed_result(res.stdout)


@pytest.fixture(scope="module")
def served():
    """AR and PARD-flat phases on the tiny self-drafting target."""
    cfg = get_config("tiny-target")
    params = load_params(cfg, jax.random.PRNGKey(0))
    prompts = make_prompts(cfg.vocab_size, 4, 12, seed=0)
    clock = smoke.CompileClock()
    recs = {}
    for name, fields in (("ar", dict(mode="ar")),
                         ("pard_flat", dict(mode="pard", k=4))):
        config = EngineConfig(max_batch=4, max_len=96, **fields)
        recs[name] = smoke.run_phase(name, config, cfg, params, cfg, params,
                                     prompts, 10, clock)
    return recs


@pytest.mark.parametrize("phase", ["ar", "pard_flat"])
def test_phase_record(served, phase):
    rec = served[phase]
    assert rec["completed"] == rec["requests"] == 4
    assert rec["full_length"] and rec["generated"] == 40
    assert rec["in_vocab"] and rec["repeat_identical"]
    assert rec["compile_s"] > 0 and rec["devices"] == 1
    assert all(len(t) == 10 for t in rec["tokens"])
    # the CPU serves through the xla reference path: no kernel in the step
    assert rec["tpu_custom_call"] is False
    with pytest.raises(smoke.SmokeFailure, match="tpu_custom_call"):
        smoke.check(rec)
    smoke.check(dict(rec, tpu_custom_call=True))


def test_greedy_pard_matches_ar(served):
    rate, first = smoke.agreement(served["ar"]["tokens"],
                                  served["pard_flat"]["tokens"])
    assert rate == 1.0 and first is None


def test_agreement_reports_first_divergence():
    ref = [np.array([1, 2, 3, 4]), np.array([5, 6, 7, 8])]
    got = [np.array([1, 2, 3, 4]), np.array([5, 6, 0, 8])]
    assert smoke.agreement(ref, got) == (7 / 8, (1, 2))


def test_check_flags_short_requests(served):
    rec = dict(served["ar"], completed=3, tpu_custom_call=True)
    with pytest.raises(smoke.SmokeFailure, match="3/4 requests"):
        smoke.check(rec)


def test_divergence_gap_ranks_the_competing_tokens(served, capsys):
    """At a (forced) divergence the diagnostic reruns the target on the
    request's prefix: the reference's greedy token leads the ranking."""
    cfg = get_config("tiny-target")
    params = load_params(cfg, jax.random.PRNGKey(0))
    prompts = make_prompts(cfg.vocab_size, 4, 12, seed=0)
    ref = served["ar"]
    tokens = [t.copy() for t in ref["tokens"]]
    tokens[1][2] = (tokens[1][2] + 1) % cfg.vocab_size
    rec = dict(served["pard_flat"], tokens=tokens, first_divergence=(1, 2))
    smoke.divergence_gap(cfg, params, prompts, ref, rec)
    line = capsys.readouterr().out.strip().splitlines()[-1]
    fields = dict(kv.split("=") for kv in line.split()[1:])
    assert fields["request"] == "1" and fields["position"] == "2"
    assert int(fields["ref_token_rank"]) <= 1
    assert int(fields["phase_token_rank"]) > 0
    assert float(fields["top1_top2_gap"]) >= 0


def test_served_logits_match_an_uncached_forward():
    """The served path's logits (prompt chunk into a paged pool, then the
    verify window) agree with one uncached forward over the same tokens;
    a permutation of them is refused."""
    from repro.models import forward
    cfg = get_config("tiny-target")
    params = load_params(cfg, jax.random.PRNGKey(0))
    prompts = make_prompts(cfg.vocab_size, 2, 20, seed=0)
    got = smoke.served_logits(cfg, params, prompts)
    assert got.shape == (2, 9, cfg.vocab_size)
    full = forward(params, cfg, np.stack(prompts))[0]
    ref = np.asarray(full, np.float32)[:, -9:, :cfg.vocab_size]
    smoke.compare_logits("served_vs_uncached", ref, got)
    with pytest.raises(smoke.SmokeFailure, match="relative error"):
        smoke.compare_logits("permuted", ref, got[:, ::-1])

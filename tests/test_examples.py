"""Example CLIs as subprocess smoke tests.

The reference's de-facto test strategy is runnable examples
(SURVEY.md §4); this repo's examples are its user-facing surface, so
each one runs here at tiny sizes — exit code, key output lines, and
the learning signal are asserted. Sizes are chosen to keep each run
under ~1 minute on the 8-virtual-CPU-device world.
"""

import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=300, tmp=None, expect_rc=0):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    argv = [sys.executable, os.path.join(_ROOT, "examples", args[0]), *args[1:]]
    if tmp is not None:  # artifact-writing examples land in tmp_path
        argv += ["--out-dir", str(tmp)]
    p = subprocess.run(
        argv, capture_output=True, text=True, timeout=timeout, env=env,
        cwd=_ROOT,
    )
    assert p.returncode == expect_rc, p.stderr[-2000:]
    return p.stdout


@pytest.mark.examples
def test_example_subgroup_parity():
    out = _run(["example_subgroup.py"])
    assert "subgroup 0 gathered: [0, 1, 2, 3]" in out
    assert "subgroup 1 gathered: [4, 5, 6, 7]" in out


@pytest.mark.examples
def test_vae_hpo_example(tmp_path):
    # --synthetic-size keeps it hermetic (no MNIST download attempt)
    # and tiny; --out-dir keeps artifacts out of the repo tree
    out = _run(["vae_hpo.py", "--epochs", "1", "--ngroups", "2",
                "--batch-size", "128", "--synthetic-size", "2048"],
               tmp=tmp_path)
    assert "trial 0 [completed]:" in out and "trial 1 [completed]:" in out
    assert "test loss" in out
    assert (tmp_path / "trial-0" / "metrics.json").exists()


@pytest.mark.examples
def test_vae_hpo_example_exits_nonzero_when_a_trial_diverges(tmp_path):
    # A diverged trial is a recorded result, not an exception; the
    # example's exit code must still say the sweep did not train.
    out = _run(["vae_hpo.py", "--epochs", "1", "--ngroups", "2",
                "--synthetic-size", "256", "--lr", "1e18"],
               tmp=tmp_path, expect_rc=1)
    assert "[diverged]" in out


@pytest.mark.examples
def test_lm_hpo_example():
    out = _run(["lm_hpo.py", "--ngroups", "2", "--seq-len", "64",
                "--steps", "12"])
    assert out.count("perplexity") == 2


@pytest.mark.examples
def test_lm_hpo_example_fused_dispatch():
    # The production dispatch shape (docs/DISPATCH.md): K fused steps
    # per device round-trip via make_lm_multi_step.
    out = _run(["lm_hpo.py", "--ngroups", "2", "--seq-len", "64",
                "--steps", "12", "--fused-steps", "4"])
    assert out.count("perplexity") == 2


@pytest.mark.examples
def test_lm_hpo_example_latent_moe():
    # the latent-attention LM with dropless routed experts as the
    # third model the example offers, its context on each trial's ring
    out = _run(["lm_hpo.py", "--ngroups", "2", "--seq-len", "64",
                "--steps", "12", "--latent-moe", "8"])
    assert out.count("perplexity") == 2
    assert out.count("assignments per expert") == 2


@pytest.mark.examples
def test_lm_long_context_example():
    out = _run(["lm_long_context.py", "--seq-len", "64", "--steps", "8"])
    assert "greedy decode matches" in out


@pytest.mark.examples
def test_lm_long_context_byte_corpus():
    out = _run(["lm_long_context.py", "--seq-len", "64", "--steps", "8",
                "--corpus", os.path.join(_ROOT, "README.md")])
    assert "byte-modeling README.md" in out
    assert "decoded:" in out


@pytest.mark.examples
def test_pbt_example(tmp_path):
    out = _run(["pbt_vae.py", "--population", "4", "--generations", "2",
                "--steps-per-generation", "4", "--synthetic-size", "512"],
               tmp=tmp_path)
    assert "best" in out.lower()
    assert "[submesh]" in out


@pytest.mark.examples
def test_pbt_example_fused(tmp_path):
    out = _run(["pbt_vae.py", "--population", "4", "--generations", "2",
                "--steps-per-generation", "4", "--synthetic-size", "512",
                "--fused"], tmp=tmp_path)
    assert "[fused]" in out
    # one fused generation program = one dispatch per generation
    assert "1.0 dispatches/gen" in out


@pytest.mark.examples
def test_resnet_hpo_example():
    out = _run(["resnet_hpo.py", "--ngroups", "2", "--epochs", "1",
                "--base-channels", "8", "--synthetic-size", "512",
                "--batch-size", "64"])
    assert out.count("test acc") == 2


@pytest.mark.examples
def test_beta_vae_cifar_example(tmp_path):
    out = _run(["beta_vae_cifar.py", "--ngroups", "4", "--epochs", "1",
                "--synthetic-size", "512", "--batch-size", "32"],
               tmp=tmp_path)
    assert "trial" in out


@pytest.mark.examples
def test_moe_vae_hpo_example(tmp_path):
    out = _run(["moe_vae_hpo.py", "--ngroups", "2", "--model-parallel",
                "2", "--epochs", "1", "--synthetic-size", "512"],
               tmp=tmp_path)
    assert "trial" in out

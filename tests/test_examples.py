"""Smoke tests for examples/ — every shipped example must run end to end
(ref: the reference CI runs example scripts in its nightly stages)."""
import os
import subprocess
import sys

import pytest

_EX = os.path.join(os.path.dirname(__file__), "..", "examples")


def _run(script, *args, timeout=420):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the parent conftest exports an 8-virtual-device XLA flag; examples
    # use small batches, so rehearse them on a 2-device mesh instead
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    res = subprocess.run([sys.executable, os.path.join(_EX, script), *args],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert res.returncode == 0, res.stdout + res.stderr
    return res.stdout


def test_mnist_mlp_example():
    out = _run("train_mnist_mlp.py", "--epochs", "1", "--batch-size", "512")
    assert "val_acc=" in out


def test_module_symbolic_example():
    out = _run("module_symbolic_mnist.py", "--epochs", "1")
    assert "validation accuracy" in out
    assert "SymbolBlock serve" in out


def test_serve_mnist_example():
    out = _run("serve_mnist.py", "--requests", "64", "--train-batches", "8")
    assert "drained=True" in out
    assert "distinct_shapes=4" in out      # bucket grid bounded the compiles


def test_serve_fleet_mnist_example():
    out = _run("serve_fleet_mnist.py", "--requests", "120",
               "--more-batches", "24")
    assert "rolling update applied=1" in out    # live weight stream landed
    assert "drained=True dropped=0" in out      # fleet-wide zero-drop drain


def test_serve_llm_example():
    out = _run("serve_llm.py", "--requests", "12", "--train-steps", "250")
    assert "drained=True" in out
    assert "0 traffic recompiles" in out      # census bounded the jit cache
    assert "pages reclaimed 32/32" in out     # paged pool fully returned


def test_bucketing_lstm_example():
    out = _run("bucketing_lstm.py", "--epochs", "2", "--batch-size", "16")
    assert "over buckets [4, 8, 12]" in out


def test_resnet_fused_example():
    out = _run("train_resnet_fused.py", "--model", "resnet18_v1",
               "--batch-size", "4", "--iters", "2", "--classes", "10")
    assert "img/s" in out


@pytest.mark.slow
def test_word_lm_example():
    out = _run("word_language_model.py", "--epochs", "1", "--batch-size",
               "8", "--embed-size", "32", "--hidden-size", "32",
               "--max-tokens", "3000")
    assert "ppl=" in out


@pytest.mark.slow
def test_bert_pretrain_example():
    out = _run("bert_pretrain.py", "--layers", "1", "--units", "64",
               "--heads", "4", "--batch-size", "2", "--seq-len", "32",
               "--num-steps", "2")
    assert "tokens/s" in out

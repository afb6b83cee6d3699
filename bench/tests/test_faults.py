"""A run with its timed path broken underneath reads ``correct`` false.

Each test drives the whole of ``bench/run.py`` on a small cell on the
CPU (the look for a chip skipped, Pallas kernels interpreted), with one
fault planted in the engine's decode step through its public wrapper
hook. A clean run of the same cell reads ``correct`` true.
"""
import json
import pathlib

import jax.numpy as jnp
import pytest

from bench import run

DATA = pathlib.Path(__file__).parent / "data"
SEED = 2 ** 31 + 4242


def one_run(capsys, hook=None, workload="mixtral-tiny.tiny"):
    rc = run.main(["--workload", workload, "--seed", str(SEED),
                   "--seconds", "2", "--trace", "0"],
                  require_tpu=False, kernel_mode="pallas_interpret",
                  engine_hook=hook, bench_file=DATA / "bench.json",
                  data=DATA)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def second_best(fn):
    """A token altered where it is produced: every decode step serves
    its runner-up token."""
    def wrapped(params, tokens, cache, pos):
        logits, new = fn(params, tokens, cache, pos)
        top = jnp.argmax(logits, axis=-1)
        return logits.at[jnp.arange(logits.shape[0]), top].set(-1e30), new
    return wrapped


def state_unchanged(fn):
    """A step that returns its state unchanged: decode computes, but the
    KV cache it wrote is dropped."""
    def wrapped(params, tokens, cache, pos):
        logits, _ = fn(params, tokens, cache, pos)
        return logits, cache
    return wrapped


@pytest.mark.parametrize("workload", ["mixtral-tiny.tiny",
                                      "qwen2-tiny.tiny"])
def test_clean_run_is_correct(capsys, workload):
    res = one_run(capsys, workload=workload)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert {"output_tokens_per_s", "ttft_p95_ms", "itl_p50_ms",
            "itl_p95_ms", "setup_s"} <= set(res["metrics"])


@pytest.mark.parametrize("fault", [second_best, state_unchanged])
def test_fault_reads_incorrect(capsys, fault):
    res = one_run(capsys, lambda eng: eng.add_decode_wrapper(fault))
    assert res["correct"] is False
    assert any(v["value"] > v["limit"] for k, v in res["checks"].items()
               if k.endswith("_gap"))

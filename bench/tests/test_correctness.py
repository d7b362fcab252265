"""The comparison that decides ``correct``, on the CPU at a small size:
the harness runs end to end (its look for a chip skipped), with the timed
path intact, broken underneath, or replaced by the float8 control."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import loadgen, reference
from bench.run import check_outputs, passed
from bench.stats import Served
from bench.tests.tiny import TINY_LIMIT, run_tiny, tiny_cell

SEEDS = (0, 1, 2)


def alter_tokens(engine):
    """Every third decode step's tokens come out one id off."""
    sample = engine._sample
    steps = {"n": 0}

    def altered(logits, key):
        steps["n"] += 1
        tok = sample(logits, key)
        return (tok + 1) % logits.shape[-1] if steps["n"] % 3 == 0 else tok
    engine._sample = altered


def frozen_cache(engine):
    """The decode step returns the cache it was given: no K/V is written."""
    decode = engine._decode

    def step(params, cache, tokens, pos):
        logits, _ = decode(params, jax.tree_util.tree_map(jnp.copy, cache),
                           tokens, pos)
        return logits, cache
    engine._decode = step


@pytest.mark.parametrize("seed", SEEDS)
def test_clean_run_is_correct_and_control_is_not(seed):
    res = run_tiny(seed, control=True)
    c = res["checks"]
    assert res["correct"], c
    assert c["tokens_compared"]["value"] >= 20
    assert c["max_logit_gap"]["value"] <= TINY_LIMIT
    # The float8 control, on the same prompts and served tokens, reads
    # above the limit: computing one precision lower would fail.
    assert c["control_max_logit_gap"]["value"] > TINY_LIMIT


@partial(jax.jit, static_argnames=("d", "fp8"))
def _next_token(params, ids, at, d, fp8):
    """The reference's greedy token after position ``at`` of ``ids``, every
    dense product in float8 when ``fp8`` (the control)."""
    h = reference._hidden(params, ids, d, fp8)[at]
    return jnp.argmax(reference._mm(h, reference._head(params, d), fp8))


def _served_by_the_reference(seed, fp8, n_requests=4):
    """The tiny cell's first requests, each served greedily by the
    reference (float8 when ``fp8``) in the program's place; returns what
    ``check_outputs`` takes."""
    cell = tiny_cell()
    d = reference.dims_of(cell.config)
    params = reference.make_params(d, seed)
    planned = loadgen.schedule(cell.traffic, seed, 2.0)[:n_requests]
    lens, max_out = loadgen.all_lengths(cell.traffic, 2.0)
    seq_len = max(lens) + max_out
    prompts, served = [], []
    for i, p in enumerate(planned):
        prompt = loadgen.prompt_tokens(seed, i, p.prompt_len, d.vocab)
        ids = np.zeros(seq_len, np.int32)
        ids[:prompt.size] = prompt
        toks = []
        for t in range(p.output_len):
            at = prompt.size - 1 + t
            toks.append(int(_next_token(params, ids, at, d, fp8)))
            ids[at + 1] = toks[-1]
        prompts.append(prompt)
        served.append(Served(i, p.due_s, p.prompt_len, p.output_len,
                             p.phase, tokens=np.asarray(toks, np.int32),
                             finished=True))
    return check_outputs(served, served, prompts, params, d, seq_len,
                         max_out, TINY_LIMIT, d.vocab)


@pytest.mark.parametrize("seed", SEEDS)
def test_control_in_the_programs_place_is_not_correct(seed):
    """The harness's own verdict on the float8 control's greedy tokens:
    not correct; on the reference's own greedy tokens: correct."""
    control = _served_by_the_reference(seed, fp8=True)
    assert control["tokens_compared"]["value"] >= 20
    assert control["max_logit_gap"]["value"] > TINY_LIMIT
    assert not passed(control)
    own = _served_by_the_reference(seed, fp8=False)
    assert own["max_logit_gap"]["value"] <= TINY_LIMIT
    assert passed(own)


@pytest.mark.parametrize("fault", [alter_tokens, frozen_cache],
                         ids=["token_altered", "state_unchanged"])
def test_planted_fault_is_not_correct(fault):
    res = run_tiny(SEEDS[0], prepare_engine=fault)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > 10 * TINY_LIMIT


@pytest.mark.parametrize("mode", ["clean", "no_psum"])
def test_exchange_between_chips(mode):
    """Four virtual devices in a process of their own (this one keeps one
    device): the sharded path is correct, and leaving out the psum of the
    row-split GEMMs is not."""
    import json
    import os
    import subprocess
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, os.path.join(here, "tp_worker.py"),
                          mode], env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["devices"] == 4
    assert res["correct"] == (mode == "clean"), res

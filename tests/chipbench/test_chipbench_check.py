"""The comparison that decides `correct`, driven through the rest of a
run at a tiny size on the CPU (the harness's look for a chip skipped):
sound runs pass, and each fault a served cell can have, planted in the
timed path, turns `correct` false. The fp8 control reads further from
the reference than the program does."""
from __future__ import annotations

import numpy as np
import jax
import pytest

from chipbench_common import TINY_TRAFFIC, run_mod, tiny_parts  # noqa: F401

# at this size the bf16 program matches the float32 reference's greedy
# token everywhere (gap 0 on the seeds below), and each fault reads
# 0.0066 or more
TINY_LIMIT = 0.003
CELL = dict(check={"sample": 64, "gap_limit": TINY_LIMIT})
SEEDS = [1, 2]


def _parts(interpret=False):
    parts = tiny_parts(cell=CELL)
    if interpret:       # the fused Pallas kernels, in the interpreter
        prog = dict(parts["config"]["program"],
                    overrides={"attention_impl": "flash_pallas"})
        parts["config"]["program"] = prog
    return parts


def state_unchanged(engine):
    """The decode step hands back the arena it was given."""
    step = engine.decode_fn

    def bad(params, arena, bt, pos, tok, st):
        _, nxt = step(params, arena, bt, pos, tok, st)
        return arena, nxt
    engine.decode_fn = bad


def token_altered(engine):
    """Row 0's token is changed where the decode step produces it."""
    step, vocab = engine.decode_fn, engine.cfg.vocab_size

    def bad(params, arena, bt, pos, tok, st):
        arena, nxt = step(params, arena, bt, pos, tok, st)
        nxt = np.asarray(nxt).copy()
        nxt[0] = (nxt[0] + 1) % vocab
        return arena, nxt
    engine.decode_fn = bad


def half_batch_dropped(engine):
    """Every other row of the decode batch walks no pages."""
    step, null = engine.decode_fn, engine.arena.null_page

    def bad(params, arena, bt, pos, tok, st):
        bt = np.asarray(bt).copy()
        bt[1::2] = null
        return step(params, arena, bt, pos, tok, st)
    engine.decode_fn = bad


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_run_is_correct(run_mod, seed):
    run = run_mod.serve_cell(_parts(), seed, 1.5, jax.devices()[:1])
    assert run["correct"], run["checks"]
    res = run_mod.result_line(_parts(), run, jax.devices()[:1], False)
    assert list(res["checks"]) == ["served_logit_gap", "checked_requests",
                                   "token_count_errors"]
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                   "output_tok_s", "setup_s"}


def test_fused_kernels_agree_with_reference(run_mod):
    run = run_mod.serve_cell(_parts(interpret=True), 3, 1.5,
                             jax.devices()[:1])
    assert run["correct"], run["checks"]
    assert run["readings"]["served_tokens"] > 20


@pytest.mark.parametrize("fault", [state_unchanged, token_altered,
                                   half_batch_dropped])
def test_fault_in_timed_path_is_not_correct(run_mod, fault):
    for seed in SEEDS:
        run = run_mod.serve_cell(_parts(), seed, 1.5, jax.devices()[:1],
                                 fault=fault)
        assert not run["correct"], (fault.__name__, seed, run["readings"])


def test_fp8_control_reads_further_than_the_program(run_mod):
    """The control goes through the same comparison as the program and
    comes out not correct at the cell's limit."""
    parts = _parts()
    B = parts["block"]
    failed = 0
    for seed in SEEDS:
        run = run_mod.serve_cell(parts, seed, 1.5, jax.devices()[:1])
        params = B.make_weights(parts["config"], seed)
        ok, _, rd = run_mod.check_served(params, B.dims(parts["config"]),
                                         run["data"], parts, seed,
                                         control=True)
        assert ok and max(rd["control"]) >= max(rd["served"])
        checks = rd["control_checks"]
        assert checks["served_logit_gap"]["value"] == max(rd["control"])
        assert rd["control_correct"] == (max(rd["control"]) <= TINY_LIMIT)
        failed += not rd["control_correct"]
    assert failed >= 1


# replies longer than the window: every checked request is in flight
LONG = dict(traffic=dict(TINY_TRAFFIC, output={"dist": "uniform", "min": 120,
                                               "max": 120}),
            cell=dict(CELL, engine={"max_batch": 4, "max_seq": 192,
                                    "page_size": 8, "pool_pages": 112}))


def test_requests_in_flight_are_checked(run_mod):
    run = run_mod.serve_cell(tiny_parts(**LONG), 4, 0.8, jax.devices()[:1])
    rd = run["readings"]
    assert run["correct"], run["checks"]
    assert rd["in_flight"] >= 1 and rd["served_tokens"] > 0
    assert run["checks"]["token_count_errors"]["value"] == 0


def test_token_altered_in_flight_is_not_correct(run_mod):
    run = run_mod.serve_cell(tiny_parts(**LONG), 4, 0.8, jax.devices()[:1],
                             fault=token_altered)
    assert run["readings"]["in_flight"] >= 1
    assert not run["correct"], run["readings"]


def test_wait_past_close_gives_every_due_request_a_first_token(run_mod):
    run = run_mod.serve_cell(_parts(), 5, 1.0, jax.devices()[:1])
    data = run["data"]
    assert data["end"] >= data["close"]
    assert set(data["first"]) == set(data["due"])
    # tokens after the close are kept for the check, not for the window
    res = run_mod.result_line(_parts(), run, jax.devices()[:1], False)
    n_window = sum(t <= data["close"] for ts in data["stamps"].values()
                   for t in ts)
    assert res["metrics"]["output_tok_s"]["value"] == n_window / (
        data["close"] - data["t0"])


FOUR_CHIPS = '''
import sys
sys.path.insert(0, "tests/chipbench")
import jax
import chipbench_common as C
from repro.distribution import collectives
run = C.load_run()
parts = C.tiny_parts(mix="longdoc", traffic=dict(
    C.TINY_TRAFFIC, clients=4, per_client=8, stagger_s=0.1),
    cell=dict(check={"sample": 64, "gap_limit": %(limit)r}))
parts["cell"]["chips"] = 4
devs = jax.devices()[:4]
sound = run.serve_cell(parts, 3, 1.5, devs)
def local_only(m, l, acc, axis, out_dtype):
    return (acc / jax.numpy.maximum(l, 1e-30)[..., None]).astype(out_dtype)
collectives.combine_shard_partials = local_only
broken = run.serve_cell(parts, 3, 1.5, devs)
print(sound["correct"], broken["correct"], max(sound["readings"]["served"]),
      max(broken["readings"]["served"]))
'''


def test_four_chips_exchange_left_out_is_not_correct():
    """The sharded arena on four virtual devices: a sound run passes, and
    with the exchange between chips left out (each chip's partial
    softmax taken as the whole) `correct` comes out false."""
    import os
    import subprocess
    import sys
    from chipbench_common import ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    # the merge across four banks rounds a little more than one arena:
    # 0.0018 at most on this seed; the broken exchange reads 0.2 and more
    out = subprocess.run([sys.executable, "-c", FOUR_CHIPS % {"limit": 0.01}],
                         cwd=ROOT, env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    sound, broken, g_sound, g_broken = out.stdout.split()[-4:]
    assert (sound, broken) == ("True", "False"), (g_sound, g_broken)


def test_compile_counter_sees_a_fresh_program():
    from harness.serve import CompileCounter
    with CompileCounter() as c:
        jax.jit(lambda x: x * 3 + 1)(np.ones(7))
    with CompileCounter() as idle:
        pass
    assert c.count >= 1 and idle.count == 0

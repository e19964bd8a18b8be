"""The check must fail a broken timed path. Each test skips the harness's
look for a chip, drives a whole reduced-width run with a fault planted
under the engine, and sees ``correct`` come out false; the float8 control
must read far above the program. The limits of these reduced cells are
the conftest's, not the chip cells'."""
import jax
import jax.numpy as jnp
import pytest

from servebench import calibrate, run

SEED = 2 ** 33 + 3
# set between the reduced cells' readings on four seeds each (CPU): program at
# most 0.009, float8 control at least 0.077
LIMITS = {"logit_gap": 0.03, "short_answers": 0}
# the benchmark configuration whose metrics each fixture reports
LIKE = {"tiny-qwen3": "qwen3-4b", "tiny-qwen3-srf": "qwen3-4b-srf"}


def _token_altered(eng):
    real = eng._sample_rows

    def altered(rows, seqs, n_pad):
        toks = real(rows, seqs, n_pad).copy()
        toks[0] = (toks[0] + 1) % eng.cfg.vocab
        return toks
    eng._sample_rows = altered


def _state_unchanged(eng):
    real = eng._step

    def stale(params, pools, *args):
        logits, _ = real(params, jax.tree.map(jnp.copy, pools), *args)
        return logits, pools
    eng._step = stale


@pytest.mark.parametrize("config", ["tiny-qwen3", "tiny-qwen3-srf"])
@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_fault_is_not_correct(cell_factory, config, fault):
    cell = cell_factory(config, "decode_long",
                        f"{LIKE[config]}.decode_long", LIMITS)
    res = run.run_cell(cell, SEED, 2.0, False, allow_cpu=True,
                       engine_hook=fault)
    assert not res["correct"]
    assert res["check"]["logit_gap"]["value"] > LIMITS["logit_gap"]


def test_spinner_fault_is_not_correct(cell_factory):
    # a quarter of the spinner's D0 signs flipped in the program alone
    cell = cell_factory("tiny-qwen3-srf", "decode_long",
                        "qwen3-4b-srf.decode_long", LIMITS)
    res = run.run_cell(cell, SEED, 2.0, False, allow_cpu=True,
                       engine_hook=calibrate.flip_d0_block(cell))
    assert not res["correct"]
    assert res["check"]["logit_gap"]["value"] > LIMITS["logit_gap"]


@pytest.mark.parametrize("config", ["tiny-qwen3", "tiny-qwen3-srf"])
def test_float8_control_is_not_correct(cell_factory, config):
    cell = cell_factory(config, "decode_long",
                        f"{LIKE[config]}.decode_long", LIMITS)
    r = calibrate.readings_for_seed(cell, SEED, 2.0, True, False)
    assert r["logit_gap"] <= LIMITS["logit_gap"] < r["control_gap"]
    assert r["control_gap"] > 3 * r["logit_gap"]


def test_no_chip_no_result(capsys):
    rc = run.main(["--workload", "qwen3-4b.decode_long", "--seed", "1",
                   "--seconds", "1"])
    assert rc == run.EXIT_DEVICE
    assert capsys.readouterr().out == ""


def test_unknown_workload_no_result(capsys):
    assert run.main(["--workload", "nope.chat", "--seed", "1",
                     "--seconds", "1"]) == run.EXIT_SPEC
    assert capsys.readouterr().out == ""


def test_witness_reads_the_same_served_tokens(cell_factory):
    # the bfloat16-state reference reads the program's served tokens and
    # puts its own tokens first; both gaps are finite readings
    cell = cell_factory("tiny-qwen3-srf", "decode_long",
                        "qwen3-4b-srf.decode_long", LIMITS)
    r = calibrate.readings_for_seed(cell, SEED, 2.0, False, False,
                                    witness=True)
    assert r["tokens"] > 0 and r["fill_s"] > 0
    for name in ("state", "features"):
        assert 0.0 <= r[f"witness_{name}_gap"] < 1e3
        assert 0.0 <= r[f"witness_{name}_pick_gap"] < 1e3

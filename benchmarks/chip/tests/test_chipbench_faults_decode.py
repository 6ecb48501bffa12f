"""A decode run whose served tokens are altered where they are produced,
or whose step leaves out its cache write or puts it at another position,
comes out not correct; the sound program and the float8 control are told
apart (the control reads, at each position of the same tokens, the gap of
the token that float8 puts first and its K/V against the reference's)."""

import jax
import numpy as np
import pytest

import chipbench_tiny as tiny
from harness import compare
from harness.kinds import decode

SEEDS = [2**32 + 9, 3]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_sound_program_is_correct(seed):
    line = tiny.run(tiny.decode_cell(), seed)
    assert line["correct"], line["compared"]
    assert {"decode_tokens_per_s", "decode_step_p95_ms", "setup_s"} <= set(line["metrics"])


@pytest.mark.parametrize("seed", SEEDS)
def test_the_float8_control_is_not_correct(seed):
    su = decode.build(tiny.decode_cell())
    params, cache, tok = decode.start(su, seed)
    served = decode.Served([np.asarray(tok)], [], [])
    decode.loop(su, params, cache, tok, served, steps=70)
    rows = decode.check_rows(su, seed)
    toks = decode.program_tokens(served, su)[rows]
    js = decode.first_lap_written(su, len(served.latency))
    ref = decode.reference_logits(su, seed, rows, toks[:, :-1])
    logits, kv = decode.reference_logits(su, seed, rows, toks[:, :-1], fp8=True)
    ctl_toks = np.concatenate([toks[:, :1], logits.argmax(-1)], axis=1)
    ok, got = compare.judge(decode.numbers(ref, ctl_toks, kv[:, :, :, js.start:js.stop], js),
                            tiny.DECODE_LIMITS)
    assert not ok, got


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    build = decode.build

    def altered(cell):
        su = build(cell)
        calls = []
        pick = jax.jit(lambda logits, bump: (su.greedy(logits) + bump) % su.m.vocab)

        def greedy(logits):
            calls.append(1)
            # one token of every row altered, at one step of the window
            return pick(logits, 1 if len(calls) == 5 else 0)

        return su._replace(greedy=greedy)

    monkeypatch.setattr(decode, "build", altered)
    line = tiny.run(tiny.decode_cell(), SEEDS[0])
    assert not line["correct"], line["compared"]


@pytest.mark.parametrize("fault", ["no_write", "misplaced"])
def test_a_cache_write_left_out_or_misplaced_is_not_correct(monkeypatch, fault):
    build = decode.build
    monkeypatch.setattr(decode, "build", lambda cell: decode.faulty(build(cell), fault))
    line = tiny.run(tiny.decode_cell(), SEEDS[0])
    assert not line["correct"], line["compared"]
    kv = line["compared"]["kv_gap"]
    assert kv["value"] > kv["limit"], line["compared"]

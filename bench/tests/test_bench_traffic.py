"""The one traffic generator: the same seed gives the same token ids,
every seed the same work with other ids, and a training step's inputs and
targets come apart from one block by a shift of one position."""
import numpy as np
import pytest

from bench import harness as H
from bench import traffic as T

MIX = H.load_json(H.BENCH / "traffic" / "paper-batch40.json")
VOCAB = 50257
BIG = 2**31 + 7


def test_block_shape_and_range():
    blk = T.train_block(MIX, VOCAB, H.Seeds(BIG).data, 0)
    assert blk.shape == (MIX["global_batch"], MIX["seq_len"] + 1)
    assert blk.dtype == np.int32
    assert blk.min() >= 0 and blk.max() < VOCAB


def test_stream_transitions():
    """Each next id follows the stream's rule, but for the mix's share of
    random ones."""
    st = MIX["stream"]
    blk = T.train_block(MIX, VOCAB, H.Seeds(BIG).data, 0).astype(np.int64)
    follows = blk[:, 1:] == (st["mult"] * blk[:, :-1] + st["add"]) % VOCAB
    assert 1 - follows.mean() == pytest.approx(st["noise"], abs=0.01)


def test_same_seed_same_block():
    seed = H.Seeds(BIG).data
    assert np.array_equal(T.train_block(MIX, VOCAB, seed, 3),
                          T.train_block(MIX, VOCAB, seed, 3))


@pytest.mark.parametrize("other", [(2**40 + 1, 0), (BIG, 1)],
                         ids=["other_seed", "other_step"])
def test_other_seed_or_step_other_ids(other):
    seed, step = other
    a = T.train_block(MIX, VOCAB, H.Seeds(BIG).data, 0)
    b = T.train_block(MIX, VOCAB, H.Seeds(seed).data, step)
    assert a.shape == b.shape
    assert np.mean(a == b) < 0.01


def test_rows_all_differ():
    blk = T.train_block(MIX, VOCAB, H.Seeds(BIG).data, 0)
    assert len({r.tobytes() for r in blk}) == len(blk)


def test_targets_are_the_next_token():
    """What the runner feeds the step, as the reference takes it apart."""
    blk = T.train_block(dict(MIX, global_batch=2, seq_len=5), VOCAB, 1, 0)
    tok, tgt = blk[:, :-1], blk[:, 1:]
    assert tok.shape == tgt.shape == (2, 5)
    for i in range(4):
        assert np.array_equal(tgt[:, i], tok[:, i + 1])

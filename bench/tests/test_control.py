"""The controls, at a size a test run holds: each train cell's control, put
in the program's place, fails at least one of the cell's limits (their
readings at the cells' own sizes, on the chip, are in PERF.md); the gossip
round's control fails the limit its tiny cell is held to."""
import pytest

import checks
import harness
import tiny
import silos
from reference import dense, mean
from test_reference import M, OPT
from trees import seed_key


@pytest.mark.parametrize("name", ["smollm-360m.train.s2048b8", "granite-3-2b.train.s4096b4"])
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_fp8_step_fails_a_train_limit(name, seed):
    limits = harness.resolve(name).limits
    batches = silos.batch_pool(vocab=M.vocab, seq_len=32, batch_per_node=4, n_nodes=1,
                               seed=seed, size=3, dirichlet_alpha=0.5, zipf_s=1.2)
    key = seed_key(seed)
    numbers = checks.train_numbers(dense.run(key, batches, M, OPT, "fp8"),
                                   dense.run(key, batches, M, OPT, "f32"))
    assert any(limits[k] is not None and numbers[k] > limits[k] for k in numbers), numbers


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_bf16_mean_fails_the_gossip_limit(seed):
    assert mean.control_gap(seed_key(seed), [(64, 32), (7,)], 4) \
        > tiny.TINY_LIMITS["gossip"]["mean_gap"]

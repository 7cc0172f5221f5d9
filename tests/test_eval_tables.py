"""EvalTables device export: field-for-field round-trip against the NumPy
evaluator tables, lazy-export caching, and the ``donate_argnums`` contract
of the jitted NSGA-II runners (the donated ``X0`` buffer must actually be
consumed, or every run holds two copies of the largest array alive)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core import nsga2_jax  # noqa: E402
from repro.core.accuracy import ProxyAccuracy  # noqa: E402
from repro.core.graph import linearize  # noqa: E402
from repro.core.partition import PartitionEvaluator  # noqa: E402
from repro.core.partition_jax import build_eval_tables  # noqa: E402
from repro.explore import PlatformSpec, SystemSpec  # noqa: E402
from repro.models.cnn.zoo import build_cnn  # noqa: E402

FOUR_PLATFORM = SystemSpec(
    platforms=(PlatformSpec("A0", "eyr", bits=16),
               PlatformSpec("A1", "eyr", bits=16),
               PlatformSpec("B0", "smb", bits=8),
               PlatformSpec("B1", "smb", bits=8)),
    links=("gige", "gige", "gige"))


@pytest.fixture(scope="module")
def evaluator():
    graph = build_cnn("efficientnet_b0", in_hw=64).to_graph()
    system = FOUR_PLATFORM.build()
    schedule = linearize(graph, "min_memory")
    return PartitionEvaluator(graph, schedule, system,
                              accuracy_fn=ProxyAccuracy(schedule, system))


def f32(x):
    return np.asarray(x, dtype=np.float32)


# -- device-export round-trip -------------------------------------------------

def test_jax_tables_roundtrip_matches_numpy(evaluator):
    """Every exported device array equals its NumPy source (after the
    documented float32 cast) — the jitted evaluator is only trustworthy if
    the tables it gathers from are bit-faithful to the host evaluator's."""
    t = evaluator.jax_tables()
    system = evaluator.system
    plats = system.platforms
    L = len(evaluator.schedule)

    assert t.L == L
    assert t.n_cuts == system.n_cuts
    assert t.batch == evaluator.batch

    np.testing.assert_array_equal(
        np.asarray(t.cost_prefix),
        f32(np.stack([evaluator._prefix[p.arch.name] for p in plats])))
    np.testing.assert_array_equal(np.asarray(t.cut_elems),
                                  f32(evaluator.cut_elements()))
    np.testing.assert_array_equal(
        np.asarray(t.producer_bpe),
        f32([p.quant.bits / 8.0 for p in plats[:-1]]))

    links = system.links
    np.testing.assert_array_equal(np.asarray(t.link_rate),
                                  f32([l.rate_bps for l in links]))
    np.testing.assert_array_equal(np.asarray(t.link_setup),
                                  f32([l.t_setup_s for l in links]))
    np.testing.assert_array_equal(np.asarray(t.link_payload),
                                  f32([l.payload_bytes for l in links]))
    np.testing.assert_array_equal(np.asarray(t.link_header),
                                  f32([l.header_bytes for l in links]))
    np.testing.assert_array_equal(np.asarray(t.link_power),
                                  f32([l.p_tx_w + l.p_rx_w for l in links]))
    np.testing.assert_array_equal(np.asarray(t.link_e_byte),
                                  f32([l.e_per_byte_j for l in links]))

    mt = evaluator._memtable
    np.testing.assert_array_equal(np.asarray(t.mem_base_prefix),
                                  f32(mt.base_prefix))
    np.testing.assert_array_equal(np.asarray(t.act_sparse),
                                  f32(mt.act_sparse))
    assert len(t.mem_groups) == len(mt.groups)
    for (jpos, jpar), (pos, par) in zip(t.mem_groups, mt.groups):
        np.testing.assert_array_equal(np.asarray(jpos),
                                      np.asarray(pos, dtype=np.int32))
        np.testing.assert_array_equal(np.asarray(jpar), f32(par))

    np.testing.assert_array_equal(
        np.asarray(t.bytes_per_param),
        f32([p.memory_model.bytes_per_param for p in plats]))
    np.testing.assert_array_equal(
        np.asarray(t.bytes_per_act),
        f32([p.memory_model.act_bytes for p in plats]))
    np.testing.assert_array_equal(np.asarray(t.capacity),
                                  f32([p.capacity for p in plats]))

    wpre, noise, base, scale = evaluator.accuracy_fn.proxy_arrays()
    assert t.supports_accuracy
    np.testing.assert_array_equal(np.asarray(t.acc_weight_prefix), f32(wpre))
    np.testing.assert_array_equal(np.asarray(t.acc_noise), f32(noise))
    assert t.acc_base == pytest.approx(float(base))
    assert t.acc_scale == pytest.approx(float(scale))


def test_jax_tables_is_cached(evaluator):
    """The export is lazy and memoized — strategies re-request it per
    search, so rebuilding would re-upload every table each time."""
    assert evaluator.jax_tables() is evaluator.jax_tables()


def test_build_eval_tables_no_accuracy_oracle():
    graph = build_cnn("efficientnet_b0", in_hw=64).to_graph()
    system = FOUR_PLATFORM.build()
    schedule = linearize(graph, "min_memory")
    ev = PartitionEvaluator(graph, schedule, system)
    t = build_eval_tables(ev)
    assert not t.supports_accuracy
    assert t.acc_weight_prefix is None and t.acc_noise is None


# -- donation contract --------------------------------------------------------

def _backend_deletes_donated():
    """Probe whether this backend honors donation by deleting the donor
    (CPU does on current jax; some backends ignore donation hints)."""
    f = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    x = jnp.ones(8)
    f(x)
    return x.is_deleted()


def test_jit_runner_donates_x0():
    """make_jit_runner's X0 really is donated: the input population buffer
    is consumed by the call, so peak memory is one population, not two."""
    if not _backend_deletes_donated():
        pytest.skip("backend does not delete donated buffers")

    def eval_fn(X):
        F = jnp.stack([X.sum(axis=1), -X.sum(axis=1)], axis=1)
        return F.astype(jnp.float32), jnp.zeros(X.shape[0], jnp.float32)

    pop, n_var = 32, 4
    run = nsga2_jax.make_jit_runner(eval_fn, n_var=n_var, lower=-1,
                                    upper=9, pop_size=pop)
    key = jax.random.PRNGKey(0)
    X0 = jnp.zeros((pop, n_var), jnp.int32)
    X, F, CV, _, counts = run(key, X0, 2)
    assert X0.is_deleted(), "X0 was not donated"
    assert not key.is_deleted(), "only argnum 1 should be donated"
    assert X.shape == (pop, n_var) and F.shape[0] == pop


def test_jit_restart_runner_donates_x0s():
    if not _backend_deletes_donated():
        pytest.skip("backend does not delete donated buffers")

    def eval_fn(X):
        F = jnp.stack([X.sum(axis=1), -X.sum(axis=1)], axis=1)
        return F.astype(jnp.float32), jnp.zeros(X.shape[0], jnp.float32)

    pop, n_var, restarts = 16, 3, 2
    run = nsga2_jax.make_jit_restart_runner(eval_fn, n_var=n_var, lower=-1,
                                            upper=9, pop_size=pop)
    keys = jax.random.split(jax.random.PRNGKey(0), restarts)
    X0s = jnp.zeros((restarts, pop, n_var), jnp.int32)
    X, F, CV, _, counts = run(keys, X0s, 2)
    assert X0s.is_deleted(), "X0s was not donated"
    assert X.shape == (restarts, pop, n_var)
    assert counts["generations"].shape == (restarts,)

"""A REPLICATED keygroup whose functions address records only by keys
the request carries: keyed one-field updates at both replicas, under a
lossy link and a partition, converge to the plain dict reference and to
a fault-free twin byte for byte, merging on the slot-aligned kernel
alone; a keygroup whose literal keys cannot take their slots still falls
back to the O(S^2) merge."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ReplicationPolicy
from repro.core import Cluster, enoki_function, get_function
from repro.core.keygroup import KeygroupSpec
from repro.core.store import arena_clone
from repro.core.versioning import fnv1a
from repro.runtime import FailureInjector

from test_keyed_kv import FF, WIDTH, DictReplica, field_update, filled

jax.config.update("jax_platform_name", "cpu")

KG, SLOTS, ROUND_MS = "kcvkg", 64, 1e6
NODES = ("edge", "edge2")


@enoki_function(name="kcv_update", keygroups=[KG], codec_width=WIDTH)
def kcv_update(kv, x):
    return field_update(kv, x)


@enoki_function(name="kcv_literal", keygroups=["kcvfullkg"], codec_width=WIDTH)
def kcv_literal(kv, x):
    kv.set("newcomer", x)
    return x[:1]


@enoki_function(name="kcv_full_update", keygroups=["kcvfullkg"],
                codec_width=WIDTH)
def kcv_full_update(kv, x):
    return field_update(kv, x)


def _cluster(kg, seed=0):
    """Both edges hold ``kg`` filled with the same seeded records,
    stamped by ``edge`` at clock 1."""
    c = Cluster({"edge": "edge", "edge2": "edge", "cloud": "cloud"},
                measure_compute=False, fault_seed=seed)
    c.create_keygroup(KeygroupSpec(name=kg, slots=SLOTS, value_width=WIDTH),
                      list(NODES))
    arena = filled(seed=seed, slots=SLOTS,
                   writer=c.nodes["edge"].node_id)
    for node in NODES:
        c.nodes[node].stores[kg] = arena_clone(arena)
    return c, arena


def _plan(seed, rounds=9):
    """Per round, (edge2's updates, edge's updates): (hash, field,
    payload) each, keys skewed over the records so both sides hit the hot
    ones.  In rounds 3-5 only ``edge`` writes: the faulty run partitions
    the link for them and heals it before round 5's drain."""
    rng = np.random.default_rng(seed)
    hashes = np.array([fnv1a(f"user{i}") for i in range(SLOTS)], np.int32)
    p = np.arange(1, SLOTS + 1, dtype=np.float64) ** -0.99
    plan = []
    for r in range(rounds):
        side = []
        for node in ("edge2", "edge"):
            n = 0 if (node == "edge2" and 3 <= r <= 5) else 5
            keys = rng.choice(SLOTS, n, p=p / p.sum())
            side.append([(np.int32(hashes[k]), np.int32(rng.integers(10)),
                          rng.standard_normal(FF, dtype=np.float32))
                         for k in keys])
        plan.append(side)
    return plan


def _run(plan, faults: bool):
    c, arena = _cluster(KG)
    c.deploy(get_function("kcv_update"), list(NODES),
             policy=ReplicationPolicy.REPLICATED,
             example_input=plan[0][1][0])
    inj = FailureInjector(c)
    if faults:
        inj.set_link_fault("edge", "edge2", drop_p=0.3, dup_p=0.3,
                           jitter_ms=2.0)
    ref = {n: DictReplica(arena, c.nodes[n].node_id) for n in NODES}
    clocks = []
    for r, sides in enumerate(plan):
        t = r * ROUND_MS
        if faults and r == 3:
            inj.partition("edge", "edge2")
        for node, ups in zip(("edge2", "edge"), sides):
            if not ups:
                continue
            res = c.invoke_batch("kcv_update", node, ups,
                                 t_sends=[t] * len(ups))
            got = [int(np.asarray(x.output)) for x in res]
            assert got == [ref[node].update_field(*u) for u in ups]
            clocks += got
        if faults and r == 5:
            inj.heal("edge", "edge2")
        assert c.drain_transport(t + 1.0)
        if not (faults and r in (3, 4)):
            a, b = ref["edge"], ref["edge2"]
            a.merge(b)
            b.merge(a)
    return c, ref, clocks


def test_keyed_updates_converge_under_faults_on_the_aligned_merge():
    plan = _plan(seed=11)
    c, ref, clocks = _run(plan, faults=True)
    twin, _, twin_clocks = _run(plan, faults=False)
    assert clocks == twin_clocks and min(clocks) > 1
    assert c.stats.repl_retries > 0
    assert c.stats.repl_dropped > 0 or c.stats.repl_duped > 0
    for node in NODES:
        store = c.store_of(KG, node)
        ref[node].assert_matches(store)
        for got, want in zip(jax.device_get(store),
                             jax.device_get(twin.store_of(KG, node))):
            np.testing.assert_array_equal(got, want)
    for cl in (c, twin):
        assert cl._aligned[KG] is True
        assert cl.stats.merge_aligned > 0 and cl.stats.merge_fallback == 0


def test_keygroup_whose_literal_keys_find_no_slot_still_falls_back():
    """A literal key registered on a full arena cannot take a slot:
    ``_register_keys`` unaligns the keygroup, and its merges take the
    O(S^2) fallback from then on, as before keys came from requests."""
    c, _ = _cluster("kcvfullkg", seed=1)
    example = (np.int32(0), np.int32(0), np.zeros(FF, np.float32))
    c.deploy(get_function("kcv_full_update"), ["edge"],
             example_input=example)
    assert c._aligned["kcvfullkg"] is True
    c.deploy(get_function("kcv_literal"), ["edge"],
             example_input=jnp.zeros(WIDTH, jnp.float32))
    assert c._aligned["kcvfullkg"] is False
    c.invoke("kcv_full_update", "edge",
             (np.int32(fnv1a("user3")), np.int32(2),
              np.ones(FF, np.float32)))
    c.flush_replication()
    assert c.stats.merge_fallback >= 1 and c.stats.merge_aligned == 0

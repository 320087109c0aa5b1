"""True elastic meshes — CPU, virtual 8-device mesh.

The ISSUE 8 tentpole surface: the surviving-device pool (re-query
discipline, seeded losses, journaled shrinks), live resharding of params /
optimizer state via ``jax.device_put``, supervisor-managed TRAINING steps
(mesh-shrink trip → rebuild over survivors → reshard → step-level replay,
bit-identical to a run pinned to the shrunken mesh, no rollback consumed),
and the train CLI ``--supervise-steps`` acceptance drill.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from cuda_mpi_gpu_cluster_programming_tpu.models.alexnet import BLOCKS12
from cuda_mpi_gpu_cluster_programming_tpu.models.init import (
    init_params_deterministic,
    init_params_random,
    random_input,
)
from cuda_mpi_gpu_cluster_programming_tpu.parallel.elastic import (
    ElasticPool,
    reshard_train_state,
    reshard_tree,
    seeded_victims,
    tree_device_ids,
)
from cuda_mpi_gpu_cluster_programming_tpu.parallel.mesh import make_mesh
from cuda_mpi_gpu_cluster_programming_tpu.resilience import chaos
from cuda_mpi_gpu_cluster_programming_tpu.resilience.journal import Journal
from cuda_mpi_gpu_cluster_programming_tpu.resilience.policy import (
    DegradationExhausted,
)
from cuda_mpi_gpu_cluster_programming_tpu.resilience.sentinel import SDC
from cuda_mpi_gpu_cluster_programming_tpu.resilience.supervisor import (
    Supervisor,
    train_ladder,
)
from cuda_mpi_gpu_cluster_programming_tpu.training import (
    make_elastic_step_builder,
    make_train_step,
)

CFG = dataclasses.replace(BLOCKS12, in_height=63, in_width=63)


def _chaos(monkeypatch, spec):
    if spec is None:
        monkeypatch.delenv(chaos.CHAOS_ENV, raising=False)
    else:
        monkeypatch.setenv(chaos.CHAOS_ENV, spec)
    chaos.reset()


@pytest.fixture(autouse=True)
def _chaos_off(monkeypatch):
    _chaos(monkeypatch, None)
    yield
    chaos.reset()


def _trees_equal(a, b) -> bool:
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


# ------------------------------------------------------------------ pool ---


def test_pool_tracks_losses_and_requeries():
    pool = ElasticPool()
    assert pool.n_total == 8 and pool.n_alive == 8 and pool.n_lost == 0
    victims = pool.alive()[5:7]
    rec = pool.lose(victims)
    assert rec["before"] == 8 and rec["after"] == 6
    assert pool.n_alive == 6 and pool.n_lost == 2
    # alive() re-queries and filters — the victims never reappear.
    assert {d.id for d in pool.alive()}.isdisjoint({d.id for d in victims})
    mesh = pool.mesh_for(4)
    assert set(mesh.devices.flat) <= set(pool.alive())
    assert pool.summary() == "6/8"


def test_pool_refuses_to_lose_all_and_unsatisfiable_mesh_raises():
    pool = ElasticPool()
    with pytest.raises(ValueError, match="refusing to lose all"):
        pool.lose(pool.alive())
    pool.lose(pool.alive()[3:])  # 8 -> 3 survivors
    with pytest.raises(ValueError, match="devices"):
        pool.mesh_for(4)  # the degrade loop's "rung unsatisfiable" signal
    assert pool.mesh_for(2).devices.size == 2


def test_pool_shrink_is_journaled(tmp_path):
    jr = Journal(tmp_path / "pool.jsonl")
    pool = ElasticPool(journal=jr, site="drill")
    pool.lose(pool.alive()[6:], cause="chaos:mesh_shrink")
    (rec,) = Journal.load(tmp_path / "pool.jsonl")
    assert rec["kind"] == "mesh_shrink"
    assert rec["before"] == 8 and rec["after"] == 6
    assert rec["cause"] == "chaos:mesh_shrink" and rec["site"] == "drill"
    assert len(rec["lost"]) == 2


def test_seeded_victims_deterministic_and_clamped():
    pool = ElasticPool()
    a = seeded_victims(pool, 3, 7)
    b = seeded_victims(pool, 3, 7)
    assert a == b and len(a) == 3
    # k is clamped so at least one device survives.
    assert len(seeded_victims(pool, 99, 7)) == 7
    # ISSUE 10 satellite (ROADMAP item 3 leftover (d)): the lowest-id /
    # default device is a LEGAL victim now — the floor builds over
    # pool.alive()[0] re-queried at trip time, so no drill spares it.
    everyone = {d.id for v in range(16) for d in seeded_victims(pool, 3, v)}
    assert pool.alive()[0].id in everyone


# ------------------------------------------------------------- grow-back ---


def test_heal_requires_fresh_roster_requery(monkeypatch):
    """The stale-device-set discipline applies to rejoin: a healed id
    leaves the exclusion set only once a FRESH jax.devices() re-query
    actually shows it; until then it stays lost and rejoin_check retries."""
    pool = ElasticPool(probation_steps=1)
    victim = pool.alive()[4]
    pool.lose([victim])
    real_devices = jax.devices
    monkeypatch.setattr(
        jax, "devices", lambda *a: [d for d in real_devices(*a) if d.id != victim.id]
    )
    rec = pool.heal([victim])
    assert rec == {"probation": [], "absent": [victim.id], "quarantined": []}
    assert pool.is_lost(victim) and pool.n_alive == 7
    # The runtime re-enumerates the device: the pending heal lands.
    monkeypatch.setattr(jax, "devices", real_devices)
    rec = pool.rejoin_check()
    assert rec["probation"] == [victim.id]
    assert not pool.is_lost(victim) and pool.is_probationary(victim)


def test_probation_excludes_from_mesh_until_graduation(tmp_path):
    jr = Journal(tmp_path / "pool.jsonl")
    pool = ElasticPool(journal=jr, probation_steps=2)
    victims = pool.alive()[5:7]
    pool.lose(victims)
    pool.heal(victims)
    # Probationary devices are healthy but NOT eligible: mesh_for must not
    # see them, alive() must not include them.
    assert pool.n_alive == 6 and pool.n_probation == 2
    assert {d.id for d in pool.alive()}.isdisjoint({d.id for d in victims})
    with pytest.raises(ValueError, match="devices"):
        pool.mesh_for(8)
    assert pool.note_clean_batch() == []  # 1 of 2 clean steps
    assert sorted(pool.note_clean_batch()) == sorted(d.id for d in victims)
    assert pool.n_alive == 8 and pool.n_probation == 0
    assert pool.mesh_for(8).devices.size == 8
    kinds = [(r["kind"], r.get("event")) for r in Journal.load(tmp_path / "pool.jsonl")]
    assert ("mesh_probation", "enter") in kinds
    assert ("mesh_probation", "pass") in kinds


def test_flap_quarantine_after_k_cycles_is_attributable(tmp_path):
    """K lose->heal cycles inside the window quarantine the device —
    journaled mesh_quarantine with the flap count — and quarantine is
    sticky: a later heal cannot resurrect it into a mesh."""
    jr = Journal(tmp_path / "pool.jsonl")
    pool = ElasticPool(journal=jr, probation_steps=2, quarantine_flaps=3)
    flapper = pool.alive()[2]
    for _ in range(2):
        pool.lose([flapper], cause="chaos:flap")
        rec = pool.heal([flapper], cause="chaos:flap")
        assert rec["probation"] == [flapper.id]
    pool.lose([flapper], cause="chaos:flap")
    rec = pool.heal([flapper], cause="chaos:flap")
    assert rec["quarantined"] == [flapper.id]
    assert pool.is_quarantined(flapper) and pool.n_alive == 7
    # sticky: healing a quarantined id is refused, never re-meshed
    rec = pool.heal([flapper])
    assert rec["quarantined"] == [flapper.id] and pool.n_alive == 7
    q = [r for r in Journal.load(tmp_path / "pool.jsonl") if r["kind"] == "mesh_quarantine"]
    assert len(q) == 1
    assert q[0]["device"] == flapper.id and q[0]["flaps"] == 3
    assert q[0]["cause"] == "chaos:flap" and q[0]["window"] == pool.flap_window


def test_floor_reached_when_device_zero_dies(tmp_path):
    """ISSUE 10 satellite (ROADMAP item 3 leftover (d)): kill the DEFAULT
    device (id 0) plus everything but one survivor; the single@1 floor must
    build over pool.alive()[0] re-queried at trip time — and the replayed
    step's state must land on that survivor, never device 0."""
    student, xs, ys = _case(steps=2)
    opt = optax.sgd(1e-3)
    sup = Supervisor(
        CFG, train_ladder(sp_shards=2),
        step_builder=make_elastic_step_builder(CFG, optimizer=opt),
        journal=Journal(tmp_path / "sup.jsonl"),
    )
    params, opt_state = student, opt.init(student)
    out = sup.supervise_step(params, opt_state, xs[0], ys[0], step=0)
    params, opt_state = out[0], out[1]
    # Kill 7 of 8 including device 0: only one non-default survivor remains.
    doomed = [d for d in sup.pool.alive() if d.id != 5]
    assert any(d.id == 0 for d in doomed)
    sup.pool.lose(doomed)
    params, opt_state = sup.trip_external(
        SDC("device_loss", 1, "drill: device 0 died"), params, opt_state
    )
    assert sup.entry.key == "single@1:reference"
    assert tree_device_ids(params) == {5}  # the floor is the SURVIVOR
    out = sup.supervise_step(params, opt_state, xs[1], ys[1], step=1)
    assert tree_device_ids(out[0]) == {5}
    # Bit-identical to runs PINNED to each topology (the elastic promise,
    # as in the promote test below): step 0 on the sp=2 rung it ran on,
    # step 1 on the single-device floor, pinned to the survivor. Not to two
    # steps on one device: two shards sum the gradient in another order.
    opt2 = optax.sgd(1e-3)
    _, step_hi = make_train_step(CFG, mesh=make_mesh(2), optimizer=opt2, sp_shards=2)
    _, step_floor = make_train_step(CFG, optimizer=opt2)
    r = step_hi(student, opt2.init(student), xs[0], ys[0])
    (survivor,) = sup.pool.alive()
    p2, o2 = jax.device_put((r[0], r[1]), survivor)
    r = step_floor(p2, o2, xs[1], ys[1])
    assert tree_device_ids(r[0]) == {5}
    assert _trees_equal(out[0], r[0])
    assert _trees_equal(out[1], r[1])


# --------------------------------------------------------------- reshard ---


def test_reshard_tree_moves_values_untouched():
    params = init_params_random(jax.random.PRNGKey(0), CFG)
    pool = ElasticPool()
    pool.lose(pool.alive()[2:3])
    mesh = pool.mesh_for(4)
    placed = reshard_tree(params, mesh)
    assert _trees_equal(params, placed)
    want = NamedSharding(mesh, P())
    for leaf in jax.tree_util.tree_leaves(placed):
        assert leaf.sharding == want
    # Placement followed the pool: no leaf lives on the lost device.
    assert tree_device_ids(placed) <= {d.id for d in pool.alive()}


def test_reshard_train_state_covers_opt_state():
    params = init_params_random(jax.random.PRNGKey(1), CFG)
    opt = optax.adam(1e-3)
    opt_state = opt.init(params)
    mesh = make_mesh(2)
    p2, o2 = reshard_train_state(params, opt_state, mesh)
    assert _trees_equal(params, p2) and _trees_equal(opt_state, o2)
    assert jax.tree_util.tree_structure(o2) == jax.tree_util.tree_structure(
        opt_state
    )
    ids = {d.id for d in mesh.devices.flat}
    assert tree_device_ids(p2) == ids and tree_device_ids(o2) == ids


# ------------------------------------------------- supervised train steps ---


def _case(sp=4, steps=3, batch=2, lr=1e-3):
    teacher = init_params_deterministic(CFG)
    teacher_fwd = jax.jit(
        lambda p, x: __import__(
            "cuda_mpi_gpu_cluster_programming_tpu.models.alexnet",
            fromlist=["forward_blocks12"],
        ).forward_blocks12(p, x, CFG)
    )
    student = init_params_random(jax.random.PRNGKey(0), CFG)
    keys = jax.random.split(jax.random.PRNGKey(9), steps)
    xs = [random_input(k, batch, CFG) for k in keys]
    ys = [teacher_fwd(teacher, x) for x in xs]
    return student, xs, ys


def test_train_ladder_shape():
    assert [e.key for e in train_ladder(sp_shards=8)] == [
        "halo@8:reference", "halo@4:reference", "halo@2:reference",
        "single@1:reference",
    ]
    assert [e.key for e in train_ladder(tp_shards=4)] == [
        "tp@4:reference", "tp@2:reference", "single@1:reference"
    ]
    assert [e.key for e in train_ladder()] == ["single@1:reference"]
    with pytest.raises(ValueError, match="mutually exclusive"):
        train_ladder(sp_shards=2, tp_shards=2)


def test_supervise_step_requires_builder():
    sup = Supervisor(CFG, train_ladder(sp_shards=2))
    with pytest.raises(ValueError, match="step_builder"):
        sup.supervise_step({}, {}, None, None)


def test_supervise_step_clean_matches_plain_step():
    student, xs, ys = _case(steps=1)
    opt = optax.sgd(1e-3)
    sup = Supervisor(
        CFG, train_ladder(sp_shards=4),
        step_builder=make_elastic_step_builder(CFG, optimizer=opt),
    )
    out = sup.supervise_step(student, opt.init(student), xs[0], ys[0], step=0)
    _, plain_step = make_train_step(
        CFG, mesh=make_mesh(4), optimizer=opt, sp_shards=4
    )
    want = plain_step(student, opt.init(student), xs[0], ys[0])
    assert sup.trips == [] and sup.replays == 0
    assert _trees_equal(out[0], want[0]) and _trees_equal(out[1], want[1])
    assert float(out[2]) == float(want[2])


def test_mesh_shrink_drill_replays_step_on_surviving_mesh(
    monkeypatch, tmp_path
):
    """The tentpole drill: mesh_shrink=2 at the first supervised step
    actually loses 2 devices, the step rebuilds on halo@2 over survivors,
    reshards live (params, opt_state), replays the SAME batch, and the
    whole 3-step trajectory is BIT-identical to an uninjected run pinned
    to the shrunken rung."""
    student, xs, ys = _case(steps=3)
    opt = optax.sgd(1e-3)
    _chaos(monkeypatch, "seed=3,mesh_shrink=2")
    sup = Supervisor(
        CFG, train_ladder(sp_shards=4),
        step_builder=make_elastic_step_builder(CFG, optimizer=opt),
        journal=Journal(tmp_path / "sup.jsonl"),
    )
    params, opt_state = student, opt.init(student)
    for i, (x, y) in enumerate(zip(xs, ys)):
        out = sup.supervise_step(params, opt_state, x, y, step=i)
        params, opt_state = out[0], out[1]
    assert [t.kind for t in sup.trips] == ["mesh_shrink"]
    assert sup.replays == 1
    assert sup.pool.n_total == 8 and sup.pool.n_alive == 6
    assert sup.entry.key == "halo@2:reference"
    kinds = [r["kind"] for r in Journal.load(tmp_path / "sup.jsonl")]
    assert kinds.count("sup_step") == 3 and kinds.count("sup_replay") == 1
    assert "mesh_shrink" in kinds  # the pool's shrink record rides along

    # Uninjected oracle pinned to the shrunken mesh: every step at sp=2.
    _chaos(monkeypatch, None)
    opt2 = optax.sgd(1e-3)
    _, step2 = make_train_step(CFG, mesh=make_mesh(2), optimizer=opt2, sp_shards=2)
    p2, o2 = student, opt2.init(student)
    for x, y in zip(xs, ys):
        out2 = step2(p2, o2, x, y)
        p2, o2 = out2[0], out2[1]
    assert _trees_equal(params, p2)
    assert _trees_equal(opt_state, o2)


def test_mesh_shrink_count_is_magnitude_one_event(monkeypatch):
    """``mesh_shrink=k`` is ONE shrink losing k devices (chaos.drain), not
    k separate trips."""
    student, xs, ys = _case(steps=2)
    opt = optax.sgd(1e-3)
    _chaos(monkeypatch, "seed=3,mesh_shrink=3")
    sup = Supervisor(
        CFG, train_ladder(sp_shards=4),
        step_builder=make_elastic_step_builder(CFG, optimizer=opt),
    )
    params, opt_state = student, opt.init(student)
    for i, (x, y) in enumerate(zip(xs, ys)):
        out = sup.supervise_step(params, opt_state, x, y, step=i)
        params, opt_state = out[0], out[1]
    assert [t.kind for t in sup.trips] == ["mesh_shrink"]  # one event
    assert sup.pool.n_alive == 5  # ... of magnitude 3


def test_supervise_step_nonfinite_loss_trips_and_degrades():
    student, xs, ys = _case(steps=1)
    opt = optax.sgd(1e-3)
    base = make_elastic_step_builder(CFG, optimizer=opt)

    def poisoned(entry, mesh):
        fn = base(entry, mesh)
        if entry.n_shards == 4:  # only the top rung is broken
            def bad(p, o, x, y):
                out = fn(p, o, x, y)
                return out[0], out[1], jnp.float32(float("nan"))

            return bad
        return fn

    sup = Supervisor(CFG, train_ladder(sp_shards=4), step_builder=poisoned)
    out = sup.supervise_step(student, opt.init(student), xs[0], ys[0], step=0)
    assert [t.kind for t in sup.trips] == ["step_nonfinite"]
    assert sup.entry.key == "halo@2:reference"
    assert np.isfinite(float(out[2]))


def test_trip_external_reshards_then_exhausts_to_caller():
    """The train loop's sentinel-trip router: each external trip degrades
    one rung and returns the resharded live state; a spent ladder raises
    DegradationExhausted (the caller's checkpoint rollback is the floor)."""
    student, _, _ = _case(steps=1)
    opt = optax.sgd(1e-3)
    ladder = train_ladder(sp_shards=4)  # 3 rungs
    sup = Supervisor(
        CFG, ladder, step_builder=make_elastic_step_builder(CFG, optimizer=opt)
    )
    params, opt_state = student, opt.init(student)
    for hop in range(len(ladder) - 1):
        params, opt_state = sup.trip_external(
            SDC("norm_spike", hop, "drill"), params, opt_state
        )
        assert _trees_equal(params, student)
    assert sup.entry.key == "single@1:reference"
    assert sup.replays == len(ladder) - 1
    with pytest.raises(DegradationExhausted):
        sup.trip_external(SDC("norm_spike", 9, "drill"), params, opt_state)


# ------------------------------------------------- grow-back: promotion ---


def test_promote_after_heal_and_probation_bit_identical(monkeypatch, tmp_path):
    """The ISSUE 10 tentpole drill (training twin): a seeded shrink trips
    halo@4 down to halo@2; a chaos device_rejoin heals the victims into
    probation; after N clean steps they graduate and maybe_promote climbs
    back to halo@4 — with the state live-resharded UP, every transition
    verified by the sentinel spot-check before adoption, and the WHOLE
    trajectory bit-identical to runs pinned to each topology (sp=2 for
    the degraded segment, sp=4 from the promoted handover on)."""
    steps = 5
    student, xs, ys = _case(steps=steps)
    opt = optax.sgd(1e-3)
    _chaos(monkeypatch, "seed=3,mesh_shrink=2,device_rejoin=2")
    jr = Journal(tmp_path / "sup.jsonl")
    sup = Supervisor(
        CFG, train_ladder(sp_shards=4),
        step_builder=make_elastic_step_builder(CFG, optimizer=opt),
        journal=jr,
    )
    params, opt_state = student, opt.init(student)
    entries = []
    for i, (x, y) in enumerate(zip(xs, ys)):
        out = sup.supervise_step(params, opt_state, x, y, step=i)
        params, opt_state = out[0], out[1]
        entries.append(sup.entry.key)
        promoted = sup.maybe_promote(params, opt_state)
        if promoted is not None:
            params, opt_state = promoted
    assert [t.kind for t in sup.trips] == ["mesh_shrink"]
    assert sup.replays == 1 and sup.promotions == 1
    assert sup.pool.n_alive == 8 and sup.pool.n_lost == 0
    assert entries[0] == "halo@2:reference"  # replayed on the shrunk rung
    assert entries[-1] == "halo@4:reference"  # climbed back
    # The incident trail reads end to end: trip -> degrade -> shrink ->
    # probation(enter) -> probation(pass) -> promote.
    records = Journal.load(tmp_path / "sup.jsonl")
    kinds = [r["kind"] for r in records]
    for a, b in [("mesh_shrink", "sup_trip"), ("sup_trip", "sup_degrade"),
                 ("sup_degrade", "mesh_probation"),
                 ("mesh_probation", "sup_promote")]:
        assert kinds.index(a) < kinds.index(b), (a, b, kinds)
    (promo,) = [r for r in records if r["kind"] == "sup_promote"]
    assert promo["frm"] == "halo@2:reference"
    assert promo["to"] == "halo@4:reference"
    assert promo["devices"] == 8 and promo["ms"] > 0
    probation = [r for r in records if r["kind"] == "mesh_probation"]
    assert [r["event"] for r in probation] == ["enter", "pass"]
    assert len(probation[0]["devices"]) == 2

    # Bit-identical to runs PINNED to each topology: the degraded segment
    # (steps 0-2, incl. the replayed step 0) matches an sp=2-pinned run,
    # and the post-promotion segment matches an sp=4-pinned run continuing
    # from that state — the reshard UP hands the exact bits over.
    _chaos(monkeypatch, None)
    assert entries == ["halo@2:reference"] * 3 + ["halo@4:reference"] * 2
    opt2 = optax.sgd(1e-3)
    _, step_lo = make_train_step(CFG, mesh=make_mesh(2), optimizer=opt2, sp_shards=2)
    _, step_hi = make_train_step(CFG, mesh=make_mesh(4), optimizer=opt2, sp_shards=4)
    p2, o2 = student, opt2.init(student)
    for k, (x, y) in enumerate(zip(xs, ys)):
        if k == 3:  # the pinned oracle's handover: same reshard-UP semantics
            p2, o2 = reshard_train_state(p2, o2, make_mesh(4))
        out2 = (step_lo if k < 3 else step_hi)(p2, o2, x, y)
        p2, o2 = out2[0], out2[1]
    assert _trees_equal(params, p2)
    assert _trees_equal(opt_state, o2)


def test_promote_refused_when_candidate_changes_results(monkeypatch, tmp_path):
    """A promotion that changes results is REFUSED, journaled
    sup_promote_refused, and never silently adopted — and the refusal
    raises the hysteresis floor so the broken candidate is not re-tried
    every batch."""
    student, xs, ys = _case(steps=4)
    opt = optax.sgd(1e-3)
    base = make_elastic_step_builder(CFG, optimizer=opt)
    builds = {"halo@4": 0}

    def poisoned(entry, mesh):
        fn = base(entry, mesh)
        if entry.key == "halo@4:reference":
            builds["halo@4"] += 1
            if builds["halo@4"] > 1:  # the REBUILT top rung computes wrong
                def bad(p, o, x, y):
                    out = fn(p, o, x, y)
                    return (out[0], out[1], out[2] * jnp.float32(1.01)) + tuple(out[3:])

                return bad
        return fn

    _chaos(monkeypatch, "seed=3,mesh_shrink=2,device_rejoin=2")
    jr = Journal(tmp_path / "sup.jsonl")
    sup = Supervisor(CFG, train_ladder(sp_shards=4), step_builder=poisoned,
                     journal=jr)
    params, opt_state = student, opt.init(student)
    for i, (x, y) in enumerate(zip(xs, ys)):
        out = sup.supervise_step(params, opt_state, x, y, step=i)
        params, opt_state = out[0], out[1]
        promoted = sup.maybe_promote(params, opt_state)
        assert promoted is None  # every candidate is refused
    assert sup.promotions == 0
    assert sup.entry.key == "halo@2:reference"  # never silently adopted
    refused = [r for r in Journal.load(tmp_path / "sup.jsonl")
               if r["kind"] == "sup_promote_refused"]
    assert len(refused) == 1  # hysteresis: refused once, not per step
    assert refused[0]["frm"] == "halo@2:reference"
    assert refused[0]["to"] == "halo@4:reference"
    assert "spot-check mismatch" in refused[0]["cause"]
    assert "sup_promote" not in [
        r["kind"] for r in Journal.load(tmp_path / "sup.jsonl")
    ]


def test_flap_drill_quarantines_never_oscillates(monkeypatch, tmp_path):
    """ISSUE 10 anti-flap acceptance: one seeded device bouncing
    lose→heal→lose must trip ONCE, then flap in probation without ever
    re-entering a mesh, end QUARANTINED after K cycles (attributable
    journal record), and the committed trajectory stays bit-identical to
    a run pinned to the degraded topology — the mesh never oscillates."""
    steps = 8
    student, xs, ys = _case(steps=steps)
    opt = optax.sgd(1e-3)
    _chaos(monkeypatch, "seed=3,flap=3")
    jr = Journal(tmp_path / "sup.jsonl")
    sup = Supervisor(
        CFG, train_ladder(sp_shards=4),
        step_builder=make_elastic_step_builder(CFG, optimizer=opt),
        journal=jr,
    )
    params, opt_state = student, opt.init(student)
    for i, (x, y) in enumerate(zip(xs, ys)):
        out = sup.supervise_step(params, opt_state, x, y, step=i)
        params, opt_state = out[0], out[1]
        assert sup.maybe_promote(params, opt_state) is None  # never climbs
    assert [t.kind for t in sup.trips] == ["mesh_shrink"]  # ONE trip
    assert sup.replays == 1 and sup.promotions == 0
    assert sup.pool.n_quarantined == 1
    assert sup.entry.key == "halo@2:reference"  # parked, not oscillating
    records = Journal.load(tmp_path / "sup.jsonl")
    (quarantine,) = [r for r in records if r["kind"] == "mesh_quarantine"]
    assert quarantine["flaps"] == sup.pool.quarantine_flaps
    assert quarantine["cause"] == "chaos:flap"
    # every committed step ran on the ONE degraded rung
    step_entries = {r["entry"] for r in records if r["kind"] == "sup_step"}
    assert step_entries == {"halo@2:reference"}

    # trajectory == uninjected run pinned to the degraded topology
    _chaos(monkeypatch, None)
    opt2 = optax.sgd(1e-3)
    _, step2 = make_train_step(CFG, mesh=make_mesh(2), optimizer=opt2, sp_shards=2)
    p2, o2 = student, opt2.init(student)
    for x, y in zip(xs, ys):
        out2 = step2(p2, o2, x, y)
        p2, o2 = out2[0], out2[1]
    assert _trees_equal(params, p2)


# ------------------------------------------------------------- train CLI ---


def _losses(out):
    return [float(l.split("loss = ")[1]) for l in out.splitlines() if "loss = " in l]


def test_train_cli_mesh_shrink_acceptance(tmp_path, capsys, monkeypatch):
    """ISSUE 8 acceptance: a seeded mesh_shrink drill during sharded
    training replays the failed step on the surviving-device mesh and
    finishes with a final param tree bit-identical to an uninjected run
    pinned to that shrunken mesh — no checkpoint rollback consumed."""
    from cuda_mpi_gpu_cluster_programming_tpu import train
    from cuda_mpi_gpu_cluster_programming_tpu.utils.checkpoint import (
        load_params_npz,
    )

    common = ["--steps", "3", "--batch", "2", "--height", "63", "--width", "63",
              "--checkpoint-every", "8"]
    _chaos(monkeypatch, "seed=3,mesh_shrink=1")
    rc = train.main(
        common + ["--sp", "4", "--supervise-steps",
                  "--work-dir", str(tmp_path / "drill"),
                  "--checkpoint", str(tmp_path / "drill.npz")]
    )
    drilled = capsys.readouterr().out
    assert rc == 0
    assert "Elastic: " in drilled and "replays=1" in drilled
    assert "kinds=mesh_shrink" in drilled and "pool=7/8" in drilled
    assert "rollback" not in drilled  # step-level replay, not the floor
    records = Journal.load(tmp_path / "drill" / "journal.jsonl")
    kinds = [r["kind"] for r in records]
    assert "sup_replay" in kinds and "mesh_shrink" in kinds
    assert "rollback" not in kinds
    assert kinds.count("step") == 3

    # Uninjected run PINNED to the shrunken mesh (sp=2, same seed/batches).
    _chaos(monkeypatch, None)
    rc = train.main(
        common + ["--sp", "2", "--work-dir", str(tmp_path / "pin"),
                  "--checkpoint", str(tmp_path / "pin.npz")]
    )
    pinned = capsys.readouterr().out
    assert rc == 0
    assert _losses(drilled) == _losses(pinned)
    assert _trees_equal(
        load_params_npz(tmp_path / "drill.npz"),
        load_params_npz(tmp_path / "pin.npz"),
    )


def test_train_cli_grow_back_acceptance(tmp_path, capsys, monkeypatch):
    """ISSUE 10 acceptance (train CLI): a seeded shrink followed by a heal
    mid-run degrades to halo@2, sits out probation, then PROMOTES back to
    halo@4 — and the final state after shrink+grow-back is bit-identical
    to a clean run's (no rollback, no restart)."""
    from cuda_mpi_gpu_cluster_programming_tpu import train
    from cuda_mpi_gpu_cluster_programming_tpu.utils.checkpoint import (
        load_params_npz,
    )

    common = ["--steps", "6", "--batch", "2", "--height", "63", "--width", "63",
              "--checkpoint-every", "8", "--sp", "4"]
    _chaos(monkeypatch, "seed=3,mesh_shrink=1,device_rejoin=1")
    rc = train.main(
        common + ["--supervise-steps", "--work-dir", str(tmp_path / "drill"),
                  "--checkpoint", str(tmp_path / "drill.npz")]
    )
    drilled = capsys.readouterr().out
    assert rc == 0
    assert "Elastic promote: climbed back to halo@4:reference" in drilled
    assert "promotions=1" in drilled and "replays=1" in drilled
    assert "pool=8/8" in drilled  # the healed device graduated back
    assert "rollback" not in drilled
    records = Journal.load(tmp_path / "drill" / "journal.jsonl")
    kinds = [r["kind"] for r in records]
    for a, b in [("sup_trip", "mesh_probation"), ("mesh_probation", "sup_promote")]:
        assert kinds.index(a) < kinds.index(b)
    assert "rollback" not in kinds
    assert kinds.count("step") == 6
    # the whole incident correlates on ONE trace (run --supervise-steps
    # traces over the work-dir journal)
    trace_ids = {r.get("trace_id") for r in records if r["kind"] in
                 ("sup_trip", "sup_promote", "mesh_probation")}
    assert len(trace_ids) == 1 and None not in trace_ids

    # Clean run, same seed/batches, never shrunk: the drilled final state
    # equals it (losses agree step for step; params within the sentinel
    # tolerance — shard-count reduction reordering costs ~1 ulp, which the
    # bit-exact topology-pinned oracle below pins down precisely).
    _chaos(monkeypatch, None)
    rc = train.main(
        common + ["--work-dir", str(tmp_path / "clean"),
                  "--checkpoint", str(tmp_path / "clean.npz")]
    )
    clean = capsys.readouterr().out
    assert rc == 0
    np.testing.assert_allclose(
        _losses(drilled), _losses(clean), rtol=1e-5, atol=0
    )
    drill_params = load_params_npz(tmp_path / "drill.npz")
    clean_params = load_params_npz(tmp_path / "clean.npz")
    for a, b in zip(
        jax.tree_util.tree_leaves(drill_params),
        jax.tree_util.tree_leaves(clean_params),
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)

    # Bit-exact acceptance vs the topology-PINNED oracle: 3 steps on the
    # degraded sp=2 rung (incl. the replayed step 0), then — after the
    # promotion hands the exact bits up — 3 steps on sp=4.
    from cuda_mpi_gpu_cluster_programming_tpu import native
    from cuda_mpi_gpu_cluster_programming_tpu.configs import (
        REGISTRY,
        build_forward,
    )

    teacher = init_params_deterministic(CFG)
    teacher_fwd = build_forward(REGISTRY["v1_jit"], CFG)
    opt2 = optax.sgd(1e-3)
    # with_grad_norm matches the CLI (sentinel on): the extra global_norm
    # in the jitted graph shifts XLA fusion by an ulp, and this oracle is
    # a BIT-exact bar.
    _, step_lo = make_train_step(
        CFG, mesh=make_mesh(2), optimizer=opt2, sp_shards=2, with_grad_norm=True
    )
    _, step_hi = make_train_step(
        CFG, mesh=make_mesh(4), optimizer=opt2, sp_shards=4, with_grad_norm=True
    )
    p2 = init_params_random(jax.random.PRNGKey(0), CFG)
    o2 = opt2.init(p2)
    shape = (2, CFG.in_height, CFG.in_width, CFG.in_channels)
    for k in range(6):
        x = native.fill_batch(shape, "uniform", native.batch_seed(0, k))
        y = teacher_fwd(teacher, x)
        if k == 3:  # the pinned oracle's handover: same reshard-UP semantics
            p2, o2 = reshard_train_state(p2, o2, make_mesh(4))
        out2 = (step_lo if k < 3 else step_hi)(p2, o2, x, y)
        p2, o2 = out2[0], out2[1]
    assert _trees_equal(drill_params, p2)


def test_train_cli_supervise_steps_requires_checkpointing(capsys):
    from cuda_mpi_gpu_cluster_programming_tpu import train

    rc = train.main(["--steps", "1", "--supervise-steps"])
    assert rc == 2
    assert "--checkpoint-every" in capsys.readouterr().err


def test_train_cli_sentinel_trip_routes_to_replay_not_rollback(
    tmp_path, capsys, monkeypatch
):
    """An injected nan_loss under --supervise-steps is answered by a
    step-level replay on the next rung — the checkpoint is never touched
    and the committed trajectory matches the clean run of the same
    ladder's SECOND rung from that step on."""
    from cuda_mpi_gpu_cluster_programming_tpu import train

    common = ["--steps", "3", "--batch", "2", "--height", "63", "--width", "63",
              "--checkpoint-every", "8", "--sp", "2"]
    _chaos(monkeypatch, "nan_loss=1")
    rc = train.main(
        common + ["--supervise-steps", "--work-dir", str(tmp_path / "w")]
    )
    out = capsys.readouterr().out
    assert rc == 0
    assert "chaos: injected nan_loss" in out
    assert "elastic replay of step 1" in out and "no rollback consumed" in out
    kinds = [r["kind"] for r in Journal.load(tmp_path / "w" / "journal.jsonl")]
    assert "rollback" not in kinds
    assert "sup_trip" in kinds and "sup_replay" in kinds
    assert kinds.count("step") == 3

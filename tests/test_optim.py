"""Optimizer update rules vs the paper's Algorithms 1-3 + baselines."""

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr
import numpy as np
import pytest

from repro.core.optim import (
    CDSGD,
    CDMSGD,
    CDMSGDNesterov,
    CDAdam,
    CentralizedSGD,
    FedAvg,
    make_optimizer,
    stacked_comm_ops,
)
from repro.core.topology import make_topology

N, D = 5, 7
ALPHA = 0.05


@pytest.fixture
def setup():
    t = make_topology("ring", N)
    comm = stacked_comm_ops(t)
    x = jnp.asarray(np.random.randn(N, D).astype(np.float32))
    g = jnp.asarray(np.random.randn(N, D).astype(np.float32))
    return t, comm, {"w": x}, {"w": g}


def test_cdsgd_matches_eq5(setup):
    """x_{k+1} = Pi x_k - alpha g  exactly (paper eq. 5)."""
    t, comm, params, grads = setup
    opt = CDSGD(ALPHA)
    st = opt.init(params)
    new, st = opt.update(params, grads, st, comm)
    want = jnp.asarray(t.pi, jnp.float32) @ params["w"] - ALPHA * grads["w"]
    np.testing.assert_allclose(np.asarray(new["w"]), np.asarray(want), rtol=2e-5, atol=2e-5)
    assert int(st.step) == 1


def test_cdmsgd_matches_algorithm2(setup):
    t, comm, params, grads = setup
    mu = 0.9
    opt = CDMSGD(ALPHA, mu=mu)
    st = opt.init(params)
    new, st = opt.update(params, grads, st, comm)
    v1 = -ALPHA * grads["w"]                      # v0 = 0
    want = jnp.asarray(t.pi, jnp.float32) @ params["w"] + v1
    np.testing.assert_allclose(np.asarray(new["w"]), np.asarray(want), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(st.inner["w"]), np.asarray(v1), rtol=1e-6)


def test_nesterov_lookahead_point(setup):
    t, comm, params, grads = setup
    opt = CDMSGDNesterov(ALPHA, mu=0.9)
    st = opt.init(params)
    # initial momentum zero -> lookahead == params
    np.testing.assert_allclose(np.asarray(opt.grad_params(params, st)["w"]),
                               np.asarray(params["w"]))
    _, st = opt.update(params, grads, st, comm)
    look = opt.grad_params(params, st)["w"]
    want = params["w"] + 0.9 * st.inner["w"]
    np.testing.assert_allclose(np.asarray(look), np.asarray(want), rtol=1e-6)


def test_cdsgd_uniform_pi_gives_mean_minus_local_grad(setup):
    _, _, params, grads = setup
    comm = stacked_comm_ops(make_topology("fully_connected", N))
    opt = CDSGD(ALPHA)
    new, _ = opt.update(params, grads, opt.init(params), comm)
    want = jnp.mean(params["w"], 0, keepdims=True) - ALPHA * grads["w"]
    np.testing.assert_allclose(np.asarray(new["w"]), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_centralized_sgd_identical_across_agents(setup):
    _, comm, params, grads = setup
    # force identical initial params across agents
    params = {"w": jnp.broadcast_to(params["w"][:1], params["w"].shape)}
    opt = CentralizedSGD(ALPHA)
    new, _ = opt.update(params, grads, opt.init(params), comm)
    spread = float(jnp.max(jnp.abs(new["w"] - new["w"][0:1])))
    assert spread < 1e-6, "centralized SGD must keep agents in lockstep"


def test_fedavg_averages_every_e_steps(setup):
    _, comm, params, grads = setup
    opt = FedAvg(ALPHA, local_steps=2)
    st = opt.init(params)
    p1, st = opt.update(params, grads, st, comm)     # step 1: local only
    assert float(jnp.max(jnp.abs(p1["w"] - p1["w"][0:1]))) > 1e-4
    p2, st = opt.update(p1, grads, st, comm)         # step 2: average
    assert float(jnp.max(jnp.abs(p2["w"] - p2["w"][0:1]))) < 1e-6


def test_fedavg_e1_equals_mean_of_local_sgd(setup):
    _, comm, params, grads = setup
    opt = FedAvg(ALPHA, local_steps=1)
    new, _ = opt.update(params, grads, opt.init(params), comm)
    want = jnp.mean(params["w"] - ALPHA * grads["w"], 0, keepdims=True)
    np.testing.assert_allclose(np.asarray(new["w"]),
                               np.broadcast_to(np.asarray(want), (N, D)), rtol=2e-5, atol=2e-5)


def test_cdadam_moments_stay_local(setup):
    t, comm, params, grads = setup
    opt = CDAdam(1e-3)
    st = opt.init(params)
    new, st = opt.update(params, grads, st, comm)
    m, v = st.inner
    np.testing.assert_allclose(np.asarray(m["w"]), 0.1 * np.asarray(grads["w"]), rtol=1e-5)
    assert new["w"].shape == (N, D)


def test_make_optimizer_registry():
    for name in ["cdsgd", "cdmsgd", "cdmsgd_nesterov", "cdadam", "sgd", "msgd", "fedavg"]:
        assert make_optimizer(name, 0.01) is not None
    with pytest.raises(ValueError):
        make_optimizer("adamw", 0.01)


def test_diminishing_schedule_drives_step_down(setup):
    from repro.core import schedules
    _, comm, params, grads = setup
    opt = CDSGD(schedules.diminishing(theta=1.0, eps=1.0, t=1.0))
    st = opt.init(params)
    alphas = []
    p = params
    for _ in range(5):
        alphas.append(float(opt.schedule(st.step)))
        p, st = opt.update(p, grads, st, comm)
    assert all(a > b for a, b in zip(alphas, alphas[1:]))


# -------------------------------------------------------------------------
# FedAvg: gated sync collective + momentum averaging (ISSUE 5 satellites)
# -------------------------------------------------------------------------


def test_fedavg_matches_handrolled_e_step_reference(setup):
    """FedAvg E=3 mu=0.9 over 7 steps vs the hand-rolled server-side
    recurrence: E local momentum-SGD steps, then BOTH x and v replaced by
    their global means.  Before this fix the local v buffers silently
    diverged across agents between syncs and were never reconciled, so
    every post-sync step immediately pulled the averaged params back
    toward each agent's own shard."""
    _, comm, params, grads = setup
    mu, e = 0.9, 3
    opt = FedAvg(ALPHA, local_steps=e, mu=mu)
    st = opt.init(params)
    p = params
    x = np.asarray(params["w"], np.float64)
    v = np.zeros_like(x)
    g = np.asarray(grads["w"], np.float64)
    for t in range(7):
        p, st = opt.update(p, grads, st, comm)
        v = mu * v - ALPHA * g
        x = x + v
        if (t + 1) % e == 0:
            x = np.broadcast_to(x.mean(0, keepdims=True), x.shape).copy()
            v = np.broadcast_to(v.mean(0, keepdims=True), v.shape).copy()
        np.testing.assert_allclose(np.asarray(p["w"]), x, rtol=0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(st.inner["w"]), v, rtol=0,
                                   atol=1e-5)


def test_fedavg_momentum_averaged_at_sync(setup):
    """The momentum buffers agree across agents right after a sync step
    (they used to keep their divergent local values forever)."""
    _, comm, params, grads = setup
    opt = FedAvg(ALPHA, local_steps=2, mu=0.9)
    st = opt.init(params)
    p, st = opt.update(params, grads, st, comm)      # local: v diverges
    assert float(jnp.max(jnp.abs(st.inner["w"] - st.inner["w"][0:1]))) > 1e-4
    p, st = opt.update(p, grads, st, comm)           # sync: v averaged
    assert float(jnp.max(jnp.abs(st.inner["w"] - st.inner["w"][0:1]))) < 1e-6


def test_fedavg_mean_gated_inside_cond(setup):
    """E>1: the averaging computation lives ONLY inside a lax.cond branch
    of the step jaxpr — the collective is paid once per E steps, i.e. 1/E
    as many mean reductions as the old unconditional mean + select.  E=1
    keeps the unconditional mean (every step syncs anyway, no cond)."""
    _, comm, params, grads = setup

    def step(e):
        opt = FedAvg(ALPHA, local_steps=e, mu=0.9)
        return jax.make_jaxpr(
            lambda p, g, s: opt.update(p, g, s, comm))(
                params, grads, FedAvg(ALPHA, local_steps=e, mu=0.9).init(params))

    def count_reduces(jaxpr, top_only):
        n = 0
        for eqn in jaxpr.eqns:
            if "reduce_sum" in eqn.primitive.name:
                n += 1
            if not top_only:
                for v in eqn.params.values():
                    for x in (v if isinstance(v, (tuple, list)) else (v,)):
                        if isinstance(x, (Jaxpr, ClosedJaxpr)):
                            j = x.jaxpr if isinstance(x, ClosedJaxpr) else x
                            n += count_reduces(j, top_only)
        return n

    j3 = step(3).jaxpr
    assert any(e.primitive.name == "cond" for e in j3.eqns)
    # the agent-mean reductions (params + momentum) exist ONLY inside the
    # cond branches — nothing averages unconditionally
    assert count_reduces(j3, top_only=True) == 0
    assert count_reduces(j3, top_only=False) >= 2
    j1 = step(1).jaxpr
    assert not any(e.primitive.name == "cond" for e in j1.eqns)
    assert count_reduces(j1, top_only=True) >= 2


def test_fedavg_sync_executions_are_one_per_e_steps(setup):
    """Runtime proof of the 1/E collective count: a callback planted in
    comm.mean fires only on the 2 sync steps of 6 jitted E=3 steps — 2
    mean calls per sync (params + momentum) x 2 syncs = 4, where the old
    unconditional averaging would have fired 6 times for params alone
    (the callback counts branch EXECUTIONS, not traces)."""
    import dataclasses as _dc
    _, comm, params, grads = setup
    fired = []

    base_mean = comm.mean

    def counting_mean(tree):
        jax.debug.callback(lambda: fired.append(1))
        return base_mean(tree)

    comm_c = _dc.replace(comm, mean=counting_mean)
    opt = FedAvg(ALPHA, local_steps=3, mu=0.9)
    step = jax.jit(lambda p, g, s: opt.update(p, g, s, comm_c))
    p, st = params, opt.init(params)
    for _ in range(6):
        p, st = step(p, grads, st)
    jax.effects_barrier()
    # 6 steps / E=3 -> 2 sync executions x 2 payload means each
    assert len(fired) == 4, fired


def test_fedavg_wire_accounting_bytes_per_e():
    """mean_exchange_bytes_per_step: the gated all-reduce amortizes to
    bytes/E per step; averaging the momentum too doubles the payloads."""
    from repro.core import flatbuf
    from repro.core.consensus import mean_exchange_bytes_per_step
    spec = flatbuf.make_flat_spec(
        {"w": jax.ShapeDtypeStruct((N, 64, 128), jnp.float32)}, lead=1)
    e1 = mean_exchange_bytes_per_step(spec, N, period=1)
    e4 = mean_exchange_bytes_per_step(spec, N, period=4)
    e4m = mean_exchange_bytes_per_step(spec, N, period=4, payloads=2)
    assert e4["per_step_bytes"] == e1["per_step_bytes"] // 4
    assert e4m["per_step_bytes"] == 2 * e4["per_step_bytes"]
    assert e1["per_sync_bytes"] == int(2 * (N - 1) / N
                                       * spec.exchange_bytes("f32"))


# -------------------------------------------------------------------------
# FedAvg partial participation (ISSUE 6 satellite): k-of-N present agents
# -------------------------------------------------------------------------


def test_fedavg_partial_participation_matches_handrolled_server(setup):
    """FedAvg E=2 mu=0.9 under a fault schedule vs the hand-rolled
    k-of-N server reference: at each sync step the server averages ONLY
    the present (non-straggling) agents — masked sum renormalized by
    N/k — and broadcasts to everyone, momentum masked identically.
    Mirrors test_fedavg_matches_handrolled_e_step_reference, which this
    reduces to when every agent is present."""
    from repro.core.faults import make_fault_schedule
    _, comm, params, grads = setup
    mu, e = 0.9, 2
    # agent 1 absent at t in {1,2,3} mod 4; agent 3 absent at t in {2,3}
    faults = make_fault_schedule("stall:1:1:3,stall:3:2:2", N)
    opt = FedAvg(ALPHA, local_steps=e, mu=mu, faults=faults)
    st = opt.init(params)
    p = params
    x = np.asarray(params["w"], np.float64)
    v = np.zeros_like(x)
    g = np.asarray(grads["w"], np.float64)
    present = ~np.asarray(faults.straggle)            # (P, N)
    saw_partial = False
    for t in range(9):
        p, st = opt.update(p, grads, st, comm)
        v = mu * v - ALPHA * g
        x = x + v
        if (t + 1) % e == 0:
            m = present[t % faults.period].astype(np.float64)
            k = m.sum()
            assert k > 0
            saw_partial = saw_partial or k < N
            x = np.broadcast_to((x * m[:, None]).sum(0, keepdims=True) / k,
                                x.shape).copy()
            v = np.broadcast_to((v * m[:, None]).sum(0, keepdims=True) / k,
                                v.shape).copy()
        np.testing.assert_allclose(np.asarray(p["w"]), x, rtol=0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(st.inner["w"]), v, rtol=0,
                                   atol=1e-5)
    assert saw_partial, "the schedule never exercised a k < N sync"


def test_fedavg_nobody_present_keeps_local_params(setup):
    """A sync step where EVERY agent straggles is a no-op sync: params
    keep their local values (no zeroing through the masked sum) and stay
    divergent across agents."""
    from repro.core.faults import make_fault_schedule
    _, comm, params, grads = setup
    spec = ",".join(f"stall:{j}:1:1" for j in range(N))
    faults = make_fault_schedule(spec, N)               # all absent at t=1
    opt = FedAvg(ALPHA, local_steps=2, mu=0.9, faults=faults)
    ref = FedAvg(ALPHA, local_steps=2, mu=0.9)
    p, st = params, opt.init(params)
    pr, str_ = params, ref.init(params)
    for _ in range(2):                                  # sync lands at t=1
        p, st = opt.update(p, grads, st, comm)
        pr, str_ = ref.update(pr, grads, str_, comm)
    # faulted run skipped the sync: agents still diverge, all finite
    assert float(jnp.max(jnp.abs(p["w"] - p["w"][0:1]))) > 1e-4
    assert bool(jnp.all(jnp.isfinite(p["w"])))
    # the fault-free reference DID average
    assert float(jnp.max(jnp.abs(pr["w"] - pr["w"][0:1]))) < 1e-6
    # ... and the faulted params equal plain 2-step local momentum SGD
    want = np.asarray(params["w"], np.float64)
    v = np.zeros_like(want)
    g = np.asarray(grads["w"], np.float64)
    for _ in range(2):
        v = 0.9 * v - ALPHA * g
        want = want + v
    np.testing.assert_allclose(np.asarray(p["w"]), want, rtol=0, atol=1e-5)

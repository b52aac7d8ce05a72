"""DecodeScheduler behavior: the step-granular continuous-batching loop.

Token-exactness against the dense per-request decode path, slot backfill and
occupancy accounting, deterministic FIFO queueing under page exhaustion,
cool-to-zero with residency accounting, EOS/deadline retirement, and the
error path that settles every future without killing the loop.

The admit and step programs update the KV page pools in place: they take
both pools donated and carry them whole through the layer loop, so a call
deletes the arrays it was given. Covered: the compiled programs alias both
pools, the AOT-loaded programs delete their inputs, the step computes
what a non-donating formulation that slices each layer's pool out computes,
and a program call that raises after consuming the pools fails every
resident request and cools the tier, so the next request boots fresh and no
call ever touches a deleted array.
"""
import dataclasses
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import FunctionSpec, Gateway
from repro.core.decode import DecodeConfig, DecodeScheduler
from repro.core.paging import PagePool
from repro.core.resilience import Deadline, DeadlineExceeded
from repro.models.transformer import EVAL_CF, _attn_block_decode_paged


@pytest.fixture(scope="module")
def dgw():
    """Cold-mode platform with the continuous-batching decode tier enabled."""
    gw = Gateway(n_hosts=2, slots_per_host=2, mode="cold", hedging=False,
                 decode=DecodeConfig(slots=3, page_size=8, cool_after_s=0.15))
    spec = FunctionSpec(arch="llama3.2-3b", batch_size=1, prompt_len=8,
                        decode_steps=12)
    gw.deploy(spec)
    yield gw, spec
    gw.shutdown()


def _dense_greedy(dep, tokens, budget):
    """The request-granular oracle: prefill + per-token greedy decode on a
    contiguous cache, exactly the math of the fused serve program."""
    model = dep.model
    params = model.init(jax.random.PRNGKey(dep.spec.seed))
    capacity = dep.spec.prompt_len + dep.spec.decode_steps
    lg, cache = model.prefill(params, {"tokens": jnp.asarray(tokens)},
                              capacity=capacity)
    toks = []
    for _ in range(budget):
        tok = jnp.argmax(lg, axis=-1)[:, None].astype(jnp.int32)
        toks.append(int(tok[0, 0]))
        lg, cache = model.decode(params, cache, tok)
    return toks


BUDGETS = [12, 3, 7, 12, 1, 5]


def test_mixed_budgets_token_exact(dgw):
    """Six requests with wildly different budgets share the step loop and each
    gets exactly its own greedy continuation — bit-identical to running it
    alone on the dense path, and exactly ``max_new`` tokens, never padded to a
    bucket's fused budget."""
    gw, spec = dgw
    dep = gw.deployments[spec.name]
    futs = [gw.invoke_decode_async(spec.name,
                                   tokens=dep.example_tokens(seed=i)[:1],
                                   max_new=b, label=f"req{i}")
            for i, b in enumerate(BUDGETS)]
    outs = [f.result(300) for f in futs]
    for i, (b, out) in enumerate(zip(BUDGETS, outs)):
        assert out.shape == (b,)
        assert out.tolist() == _dense_greedy(
            dep, dep.example_tokens(seed=i)[:1], b)
    s = gw.decode_summary(spec.name)
    assert s["requests"] >= len(BUDGETS)
    assert s["admits"] >= len(BUDGETS)
    assert s["tokens_generated"] >= sum(BUDGETS)
    # step-granular: total steps is bounded by the per-request sum, and the
    # early-finishing rows never hold their slot for even one extra step
    assert s["steps"] < sum(BUDGETS)
    assert s["occupancy"] > 0.25
    assert s["page_alloc_failures"] == 0


def test_timelines_carry_ttfr(dgw):
    gw, spec = dgw
    gw.invoke_decode(spec.name, max_new=3, label="ttfr-probe")
    tls = gw.recorder.timelines("ttfr-probe")
    assert tls
    tl = tls[-1]
    assert tl.t_ttfr is not None
    # first token lands at admit — before the last step retires the request
    assert tl.t_exec_begin <= tl.t_ttfr <= tl.t_done


def test_eos_retires_early(dgw):
    gw, spec = dgw
    dep = gw.deployments[spec.name]
    toks = _dense_greedy(dep, dep.example_tokens(seed=99)[:1], 6)
    eos = toks[2]
    want = toks[:toks.index(eos) + 1]
    sched = DecodeScheduler(
        dep, gw.cluster, gw.recorder,
        DecodeConfig(slots=2, page_size=8, cool_after_s=0.1, eos_token=eos))
    try:
        out = sched.submit(dep.example_tokens(seed=99)[:1]).result(300)
    finally:
        sched.close()
    assert out.tolist() == want
    assert sched.pool.used_pages == 0


def test_cool_to_zero_and_reboot(dgw):
    gw, spec = dgw
    dec = gw.decoders[spec.name]
    res0 = gw.residency.summary()["total_GBs"]
    gw.invoke_decode(spec.name, max_new=2)
    boots0, cools0 = dec.boots, dec.cooldowns
    deadline = time.time() + 10
    # wait on the counter, not _ex: _cool() clears _ex before it finishes
    # accounting, so _ex going None only means the cooldown has BEGUN
    while dec.cooldowns < cools0 + 1 and time.time() < deadline:
        time.sleep(0.05)
    assert dec.cooldowns >= cools0 + 1
    assert dec._ex is None, "decode executor must cool to ZERO after quiet"
    # the cooled executor's residency landed in the platform tracker
    assert gw.residency.summary()["total_GBs"] > res0
    # the next burst pays a fresh boot — no warm remnant survived
    out = gw.invoke_decode(spec.name, max_new=2)
    assert out.shape == (2,)
    assert dec.boots == boots0 + 1


def test_page_exhaustion_queues_fifo_without_corruption(dgw):
    """Shrink the accounting pool so only ONE request's reservation fits: the
    queue head waits (admit-or-queue), later requests never jump it, and every
    serialized request still decodes token-exactly."""
    gw, spec = dgw
    dep = gw.deployments[spec.name]
    sched = DecodeScheduler(dep, gw.cluster, gw.recorder,
                            DecodeConfig(slots=3, page_size=8,
                                         cool_after_s=0.1))
    sched.pool = PagePool(4, 8)      # 3 allocatable pages = one 20-token chain
    order = []
    try:
        futs = []
        for i in range(3):
            fut = sched.submit(dep.example_tokens(seed=i)[:1], max_new=12)
            fut.add_done_callback(lambda _f, i=i: order.append(i))
            futs.append(fut)
        outs = [f.result(300) for f in futs]
    finally:
        sched.close()
    for i, out in enumerate(outs):
        assert out.tolist() == _dense_greedy(
            dep, dep.example_tokens(seed=i)[:1], 12)
    assert order == [0, 1, 2]                     # FIFO, no starvation
    assert sched.admit_waits >= 1                 # head actually waited
    assert sched.pool.alloc_failures >= 1
    assert sched.steps == sched.step_rows         # one resident at a time
    assert sched.pool.used_pages == 0


def test_submit_rejects_malformed_and_oversized(dgw):
    gw, spec = dgw
    dep = gw.deployments[spec.name]
    dec = gw.decoders[spec.name]
    bad = dec.submit(np.zeros((2, spec.prompt_len), np.int32))
    with pytest.raises(ValueError, match="prompt must be"):
        bad.result(1)
    # a worst case no reservation can cover is rejected synchronously, not
    # left to spin at the queue head forever
    big = DecodeScheduler(dep, gw.cluster, gw.recorder,
                          DecodeConfig(slots=3, page_size=8, max_new=1000))
    try:
        with pytest.raises(ValueError, match="pages"):
            big.submit(dep.example_tokens()[:1]).result(1)
    finally:
        big.close()


def test_expired_deadline_settles_the_future(dgw):
    gw, spec = dgw
    fut = gw.invoke_decode_async(spec.name, max_new=3, deadline_s=1e-6)
    with pytest.raises(DeadlineExceeded):
        fut.result(300)
    # the loop is still healthy afterwards
    assert gw.invoke_decode(spec.name, max_new=1).shape == (1,)


def test_step_failure_settles_futures_and_loop_survives(dgw):
    gw, spec = dgw
    dep = gw.deployments[spec.name]
    sched = DecodeScheduler(dep, gw.cluster, gw.recorder,
                            DecodeConfig(slots=2, page_size=8,
                                         cool_after_s=0.1))
    real = sched.bundle

    def boom(*_a, **_k):
        raise RuntimeError("injected step failure")

    sched.bundle = dataclasses.replace(real, step=boom)
    try:
        fut = sched.submit(dep.example_tokens(seed=7)[:1], max_new=4)
        with pytest.raises(RuntimeError, match="injected"):
            fut.result(300)
        assert sched.pool.used_pages == 0         # pages released on failure
        sched.bundle = real                       # next burst: fresh boot
        out = sched.submit(dep.example_tokens(seed=7)[:1], max_new=4).result(300)
    finally:
        sched.close()
    assert out.tolist() == _dense_greedy(dep, dep.example_tokens(seed=7)[:1], 4)


def test_close_during_inflight_admit_settles_the_future(dgw):
    """A request mid-admit is in neither ``_queue`` nor ``_slots`` — drain()
    (and so close()) must still see it via the in-flight count and wait, or
    close() cools the executor under the prefill and the future never
    settles."""
    gw, spec = dgw
    dep = gw.deployments[spec.name]
    sched = DecodeScheduler(dep, gw.cluster, gw.recorder,
                            DecodeConfig(slots=2, page_size=8,
                                         cool_after_s=0.1))
    real = sched.bundle
    started = threading.Event()

    def slow_admit(*a, **k):
        started.set()
        time.sleep(0.3)                   # hold the request in the admit gap
        return real.admit(*a, **k)

    sched.bundle = dataclasses.replace(real, admit=slow_admit)
    # budget > 1 extra step so retirement spans several loop iterations —
    # close() must wait through the admit AND the remaining steps
    fut = sched.submit(dep.example_tokens(seed=3)[:1], max_new=6)
    assert started.wait(60)
    sched.close()                         # races the in-flight admit
    out = fut.result(1)                   # settled BEFORE close() returned
    assert out.tolist() == _dense_greedy(dep, dep.example_tokens(seed=3)[:1], 6)
    assert sched.pool.used_pages == 0
    assert sched._ex is None


def test_submit_rejects_out_of_range_max_new(dgw):
    """max_new is validated, never clamped: over-budget asks fail loudly
    instead of returning silently truncated output, and 0 (admit always emits
    one token) is an error, not the full default budget."""
    gw, spec = dgw
    dec = gw.decoders[spec.name]
    for bad in (0, -1, spec.decode_steps + 1):
        with pytest.raises(ValueError, match="max_new must be in"):
            dec.submit(gw.deployments[spec.name].example_tokens()[:1],
                       max_new=bad).result(1)
    # None still means the full deploy budget
    out = gw.invoke_decode(spec.name)
    assert out.shape == (spec.decode_steps,)


def test_redeploy_closes_the_old_decoder():
    """Re-deploying a name must drain + cool the old scheduler, not leak its
    loop thread and executor outside the residency accounting."""
    gw = Gateway(n_hosts=1, slots_per_host=2, mode="cold", hedging=False,
                 decode=DecodeConfig(slots=2, page_size=8, cool_after_s=0.1))
    try:
        spec = FunctionSpec(arch="llama3.2-3b", batch_size=1, prompt_len=8,
                            decode_steps=4)
        gw.deploy(spec)
        old = gw.decoders[spec.name]
        gw.invoke_decode(spec.name, max_new=2)
        gw.deploy(spec)
        new = gw.decoders[spec.name]
        assert new is not old
        assert not old._running
        assert old._ex is None
        assert not old._thread.is_alive()
        assert gw.invoke_decode(spec.name, max_new=2).shape == (2,)
    finally:
        gw.shutdown()


def test_boot_failure_after_start_exits_the_executor(dgw, monkeypatch):
    """If post-start setup (page-pool init) fails, the started executor must
    be exited with its residency accounted — not silently leaked off
    ``self._ex``."""
    gw, spec = dgw
    dep = gw.deployments[spec.name]
    exited = []
    sched = DecodeScheduler(dep, gw.cluster, gw.recorder,
                            DecodeConfig(slots=2, page_size=8,
                                         cool_after_s=0.1),
                            on_exit=exited.append)
    real_init = type(dep.model).init_page_pool
    fail = {"on": True}

    def flaky_init(self, *a, **k):
        if fail["on"]:
            raise RuntimeError("injected pool-init failure")
        return real_init(self, *a, **k)

    monkeypatch.setattr(type(dep.model), "init_page_pool", flaky_init)
    try:
        fut = sched.submit(dep.example_tokens(seed=5)[:1], max_new=2)
        with pytest.raises(RuntimeError, match="injected pool-init"):
            fut.result(300)
        assert len(exited) == 1               # started executor was exited...
        assert sched._ex is None              # ...and never published
        assert sched.pool.used_pages == 0
        fail["on"] = False
        # the loop survived the failed boot AND the per-request error path
        out = sched.submit(dep.example_tokens(seed=5)[:1], max_new=2).result(300)
        assert out.tolist() == _dense_greedy(
            dep, dep.example_tokens(seed=5)[:1], 2)
    finally:
        sched.close()


def test_decode_bundle_is_a_deploy_time_artifact(dgw):
    gw, spec = dgw
    dep = gw.deployments[spec.name]
    b1 = dep.ensure_decode(3, 8)
    b2 = dep.ensure_decode(3, 8)
    assert b1 is b2                               # compiled once, ever
    assert b1.aot_verified                        # serialized + reloaded
    assert b1.pools_in_place                      # both alias both pools
    assert b1.n_pages == 1 + b1.slots * b1.max_pages


# ------------------------------------------------------ pools updated in place

def _sliced_step(model, params, k_pages, v_pages, table, pos, token):
    """A non-donating formulation of the step: the layer scan slices each
    layer's pool out as ``xs``, writes and attends on that slice alone (as a
    one-layer pool, layer 0) and stacks the slices back as ``ys``."""
    cfg = model.cfg
    x = model._embed(params, {}, token, pos_offset=pos)

    def body(xx, inp):
        p_l, k_l, v_l = inp
        xx, k2, v2 = _attn_block_decode_paged(cfg, p_l, xx, k_l[None], v_l[None],
                                              table, pos, 0, EVAL_CF)
        return xx, (k2[0], v2[0])

    x, (ks, vs) = jax.lax.scan(body, x, (params["stack"]["layers"], k_pages,
                                         v_pages))
    return model._head(params, x)[:, 0], ks, vs


def _admitted(dep, bundle, params, n_rows):
    """Fresh pools with ``n_rows`` prompts admitted through the bundle, and
    the step inputs (table, pos, token) for every admitted row's next token."""
    pools = dep.model.init_page_pool(bundle.n_pages, bundle.page_size)
    k, v = pools["k_pages"], pools["v_pages"]
    mp = bundle.max_pages
    table = np.zeros((bundle.slots, mp), np.int32)
    pos = np.zeros((bundle.slots,), np.int32)
    tok = np.zeros((bundle.slots, 1), np.int32)
    for r in range(n_rows):
        table[r] = 1 + r * mp + np.arange(mp)
        prompt = dep.example_tokens(seed=r)[:1]
        logits, k, v = bundle.admit(params, prompt, k, v, table[r])
        pos[r] = prompt.shape[1]
        tok[r, 0] = int(np.argmax(np.asarray(logits, np.float32)))
    return k, v, table, pos, tok


def test_a_call_deletes_the_pools_it_was_given(dgw):
    gw, spec = dgw
    dep = gw.deployments[spec.name]
    bundle = dep.ensure_decode(3, 8)
    params = dep.model.init(jax.random.PRNGKey(spec.seed))
    pools = dep.model.init_page_pool(bundle.n_pages, bundle.page_size)
    k, v = pools["k_pages"], pools["v_pages"]
    table = np.zeros((bundle.slots, bundle.max_pages), np.int32)
    table[0] = 1 + np.arange(bundle.max_pages)
    _, k2, v2 = bundle.admit(params, dep.example_tokens()[:1], k, v, table[0])
    assert k.is_deleted() and v.is_deleted()
    pos = np.zeros((bundle.slots,), np.int32)
    pos[0] = spec.prompt_len
    _, k3, v3 = bundle.step(params, k2, v2, table, pos,
                            np.zeros((bundle.slots, 1), np.int32))
    assert k2.is_deleted() and v2.is_deleted()
    assert not (k3.is_deleted() or v3.is_deleted())


def test_step_equals_the_slice_per_layer_formulation(dgw):
    gw, spec = dgw
    dep = gw.deployments[spec.name]
    bundle = dep.ensure_decode(3, 8)
    model = dep.model
    params = model.init(jax.random.PRNGKey(spec.seed))
    k, v, table, pos, tok = _admitted(dep, bundle, params, n_rows=2)
    want_logits, want_k, want_v = jax.jit(
        lambda *a: _sliced_step(model, *a))(params, k, v, table, pos, tok)
    got_logits, got_k, got_v = bundle.step(params, k, v, table, pos, tok)
    np.testing.assert_array_equal(np.asarray(got_logits[:2], np.float32),
                                  np.asarray(want_logits[:2], np.float32))
    # the pools compare at every page the rows own; page 0 takes the empty
    # slots' writes, in an order neither formulation fixes
    live = table[:2].reshape(-1)
    for got, want in ((got_k, want_k), (got_v, want_v)):
        np.testing.assert_array_equal(np.asarray(got[:, live], np.float32),
                                      np.asarray(want[:, live], np.float32))


def _guarded(fn, touched):
    """``fn``, recording every call that is handed an array already deleted."""
    def call(*args, **kw):
        if any(isinstance(a, jax.Array) and a.is_deleted() for a in args):
            touched.append(fn)
        return fn(*args, **kw)
    return call


def test_admit_failure_after_consuming_the_pools_reboots(dgw):
    """The second admit consumes the pools and then raises: that request
    fails, and so does the resident one, since no pools are left to step
    it on; the tier cools, and the next request boots fresh and is served
    exactly."""
    gw, spec = dgw
    dep = gw.deployments[spec.name]
    sched = DecodeScheduler(dep, gw.cluster, gw.recorder,
                            DecodeConfig(slots=2, page_size=8,
                                         cool_after_s=0.1))
    real = sched.bundle
    touched = []
    second_queued = threading.Event()
    calls = []

    def admit(*a, **k):
        calls.append(1)
        if len(calls) == 1:
            # hold the first admission until the second request is queued,
            # so the second one's admit runs with the first one resident
            assert second_queued.wait(60)
            return real.admit(*a, **k)
        real.admit(*a, **k)
        raise RuntimeError("injected admit failure")

    sched.bundle = dataclasses.replace(real, admit=_guarded(admit, touched),
                                       step=_guarded(real.step, touched))
    try:
        resident = sched.submit(dep.example_tokens(seed=1)[:1], max_new=12)
        failing = sched.submit(dep.example_tokens(seed=2)[:1], max_new=4)
        second_queued.set()
        for fut in (failing, resident):
            with pytest.raises(RuntimeError, match="injected admit failure"):
                fut.result(300)
        assert sched.pool.used_pages == 0
        assert sched.boots == 1
        sched.bundle = dataclasses.replace(real, admit=_guarded(real.admit, touched),
                                           step=_guarded(real.step, touched))
        out = sched.submit(dep.example_tokens(seed=3)[:1], max_new=5).result(300)
    finally:
        sched.close()
    assert out.tolist() == _dense_greedy(dep, dep.example_tokens(seed=3)[:1], 5)
    assert sched.boots == 2
    assert touched == []


def test_deadline_rejection_at_admit_stays_per_request(dgw):
    """A request whose deadline passed before its admit fails alone: the
    pools were never handed to a program, so the resident request runs on."""
    gw, spec = dgw
    dep = gw.deployments[spec.name]
    sched = DecodeScheduler(dep, gw.cluster, gw.recorder,
                            DecodeConfig(slots=2, page_size=8,
                                         cool_after_s=0.1))
    real = sched.bundle
    late_queued = threading.Event()

    def admit(*a, **k):
        assert late_queued.wait(60)
        return real.admit(*a, **k)

    sched.bundle = dataclasses.replace(real, admit=admit)
    try:
        resident = sched.submit(dep.example_tokens(seed=4)[:1], max_new=8)
        late = sched.submit(dep.example_tokens(seed=5)[:1], max_new=4,
                            deadline=Deadline.after(-1.0))
        late_queued.set()
        with pytest.raises(DeadlineExceeded):
            late.result(300)
        out = resident.result(300)
    finally:
        sched.close()
    assert out.tolist() == _dense_greedy(dep, dep.example_tokens(seed=4)[:1], 8)
    assert sched.boots == 1

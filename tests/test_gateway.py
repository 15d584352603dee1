"""Network gateway (deepspeed_tpu/gateway/): protocol units — request
parse/validate, SSE framing, Retry-After math, the SLO-class map —
plus loopback integration against a real spawned gateway: stream
parity with in-process ``generate()``, fleet-backed routing, 429
under saturation, disconnect->cancel, ``/healthz`` + ``/metrics``
round-trips through the existing Prometheus parser, the drain
contract, and the dead-engine start refusal.

The heavier wire legs (greedy+seeded parity over a full seeded trace,
the disconnect/drain chaos variants) are tier-1 via
``tools/loadgen.py --http`` / ``--http-chaos`` in test_loadgen; this
file owns the protocol surface and the per-feature integration paths.
"""

import json
import socket
import threading
import time

import pytest

from deepspeed_tpu.gateway import (GatewayConfig, GatewayError,
                                   default_slo_classes, resolve_slo,
                                   spawn_gateway)
from deepspeed_tpu.gateway import protocol
from deepspeed_tpu.inference import SamplingParams
from deepspeed_tpu.inference.overload import OverloadConfig
from deepspeed_tpu.telemetry import parse_prometheus_text
from tools.loadgen import build_engine, build_fleet, http_completion, http_get


# ==========================================================================
# protocol units (no sockets, no engine)
# ==========================================================================

class TestRequestHead:
    def test_parses_method_target_headers(self):
        head = (b"POST /v1/completions HTTP/1.1\r\n"
                b"Host: x\r\nContent-Length: 12\r\n"
                b"X-SLO-Class: interactive\r\n")
        method, target, headers = protocol.parse_request_head(head)
        assert method == "POST"
        assert target == "/v1/completions"
        # names lowercased (case-insensitive), values stripped
        assert headers["content-length"] == "12"
        assert headers["x-slo-class"] == "interactive"

    @pytest.mark.parametrize("head", [
        b"GET\r\n",                          # no target/version
        b"GET / HTTP/1.1 extra\r\n",         # 4-part request line
        b"GET / SPDY/3\r\n",                 # not HTTP/1.x
        b"GET / HTTP/1.1\r\n bad header\r\n",  # leading-space header name
        "GET /é HTTP/1.1\r\n".encode("utf-8"),  # non-ASCII bytes
    ])
    def test_rejects_malformed(self, head):
        with pytest.raises(protocol.ProtocolError) as ei:
            protocol.parse_request_head(head)
        assert ei.value.status == 400


class TestCompletionBody:
    def _parse(self, obj, default=16, cap=512):
        return protocol.parse_completion_body(
            json.dumps(obj).encode(), default, cap)

    def test_minimal_body_and_defaults(self):
        req = self._parse({"prompt": [1, 2, 3]})
        assert req.prompt == [1, 2, 3]
        assert req.max_tokens == 16          # server default
        assert req.stream is False and req.uid is None
        assert req.priority is None and req.deadline_ms is None

    def test_full_body(self):
        req = self._parse({"prompt": [4], "max_tokens": 3, "stream": True,
                           "uid": 9, "priority": 2, "deadline_ms": 500})
        assert (req.max_tokens, req.stream, req.uid, req.priority,
                req.deadline_ms) == (3, True, 9, 2, 500.0)

    def test_max_tokens_capped_not_rejected(self):
        assert self._parse({"prompt": [1], "max_tokens": 10_000},
                           cap=64).max_tokens == 64

    def test_unknown_fields_ignored(self):
        req = self._parse({"prompt": [1], "model": "gpt-x",
                           "temperature": 0.7, "logprobs": 5})
        assert req.prompt == [1]

    @pytest.mark.parametrize("body,code", [
        ({}, "bad_prompt"),
        ({"prompt": "hello"}, "bad_prompt"),       # tokenizer-free stack
        ({"prompt": []}, "bad_prompt"),
        ({"prompt": [1, True]}, "bad_prompt"),     # bools are not tokens
        ({"prompt": [1], "max_tokens": 0}, "bad_max_tokens"),
        ({"prompt": [1], "max_tokens": "4"}, "bad_max_tokens"),
        ({"prompt": [1], "stream": 1}, "bad_stream"),
        ({"prompt": [1], "uid": -3}, "bad_uid"),
        ({"prompt": [1], "priority": 1.5}, "bad_priority"),
        ({"prompt": [1], "deadline_ms": -1}, "bad_deadline"),
    ])
    def test_rejects_bad_fields(self, body, code):
        with pytest.raises(protocol.ProtocolError) as ei:
            self._parse(body)
        assert ei.value.code == code
        assert ei.value.status == 400

    def test_rejects_non_json(self):
        with pytest.raises(protocol.ProtocolError) as ei:
            protocol.parse_completion_body(b"{nope", 16, 512)
        assert ei.value.code == "bad_json"
        with pytest.raises(protocol.ProtocolError):
            protocol.parse_completion_body(b"[1,2]", 16, 512)


class TestSloMap:
    def test_class_defaults_fill_unset_fields(self):
        classes = default_slo_classes()
        pri, dl, name = resolve_slo("interactive", classes, "standard",
                                    None, None)
        assert (pri, dl, name) == (0, 30_000.0, "interactive")
        pri, dl, name = resolve_slo("batch", classes, "standard",
                                    None, None)
        assert (pri, dl, name) == (2, None, "batch")

    def test_absent_header_takes_default_class(self):
        pri, dl, name = resolve_slo(None, default_slo_classes(),
                                    "standard", None, None)
        assert (pri, name) == (1, "standard")

    def test_explicit_fields_beat_class_defaults(self):
        pri, dl, _ = resolve_slo("interactive", default_slo_classes(),
                                 "standard", 3, 99.0)
        assert (pri, dl) == (3, 99.0)

    def test_unknown_class_raises(self):
        with pytest.raises(KeyError):
            resolve_slo("platinum", default_slo_classes(), "standard",
                        None, None)


class TestShedTranslation:
    def test_retry_after_scales_with_depth_and_clamps(self):
        # 1 request @ 250 ms -> ceil(0.25) = 1 s
        assert protocol.retry_after_s(1, 250.0, 30) == 1
        # 20 requests @ 250 ms -> 5 s
        assert protocol.retry_after_s(20, 250.0, 30) == 5
        # clamped to the ceiling
        assert protocol.retry_after_s(10_000, 250.0, 30) == 30
        # never 0, even with no backlog
        assert protocol.retry_after_s(0, 250.0, 30) == 1

    def test_policy_shed_is_429_with_computed_backoff(self):
        code, ra, slug = protocol.shed_decision(
            "shed", "admission queue bound", 20, 250.0, 30, 5)
        assert (code, ra, slug) == (429, 5, "overloaded")

    def test_dead_and_draining_are_503_with_drain_horizon(self):
        for reason in ("engine is dead", "engine is draining"):
            code, ra, slug = protocol.shed_decision(
                "shed", reason, 20, 250.0, 30, 7)
            assert (code, ra, slug) == (503, 7, "unavailable")

    def test_fleet_reason_split_saturation_429_vs_no_replica_503(self):
        # fleet saturation (router.py verdict): every ROUTABLE replica's
        # own bound shed it — that is load, retry after backoff helps
        code, _, _ = protocol.shed_decision(
            "shed", "fleet saturated: every routable replica shed the "
            "request", 4, 250.0, 30, 7)
        assert code == 429
        # an all-dead/quarantined fleet: availability, not load — a
        # 429 backoff loop against zero replicas helps nobody
        code, ra, _ = protocol.shed_decision(
            "shed", "no routable replica", 4, 250.0, 30, 7)
        assert (code, ra) == (503, 7)

    def test_unknown_non_admission_maps_conservatively_503(self):
        code, _, _ = protocol.shed_decision("mystery", "", 1, 250.0, 30, 5)
        assert code == 503

    def test_health_ladder_status_codes(self):
        assert protocol.health_status_code("healthy") == 200
        assert protocol.health_status_code("degraded") == 200
        assert protocol.health_status_code("draining") == 503
        assert protocol.health_status_code("dead") == 503


class TestFraming:
    def test_sse_event_bytes(self):
        b = protocol.sse_event({"a": 1})
        assert b == b'data: {"a":1}\n\n'

    def test_completion_chunk_shape(self):
        ch = protocol.completion_chunk("cmpl-7", 123, "m", token=42)
        assert ch["object"] == "text_completion.chunk"
        assert ch["choices"][0]["token"] == 42
        assert ch["choices"][0]["finish_reason"] is None
        fin = protocol.completion_chunk("cmpl-7", 123, "m",
                                        finish_reason="length")
        assert fin["choices"][0]["token"] is None
        assert fin["choices"][0]["finish_reason"] == "length"

    def test_http_response_framing(self):
        raw = protocol.http_response(429, b'{"e":1}',
                                     extra_headers={"Retry-After": "3"})
        head, _, body = raw.partition(b"\r\n\r\n")
        assert head.startswith(b"HTTP/1.1 429 Too Many Requests")
        assert b"Content-Length: 7" in head
        assert b"Connection: close" in head
        assert b"Retry-After: 3" in head
        assert body == b'{"e":1}'


# ==========================================================================
# loopback integration
# ==========================================================================

@pytest.fixture(scope="module")
def model():
    return build_engine()[1]


@pytest.fixture(scope="module")
def gw(model):
    """One greedy gateway over a tiny engine, shared by the
    integration tests below (spawn + first-step compile are the
    expensive parts)."""
    eng, _ = build_engine(model=model)
    h = spawn_gateway(eng, GatewayConfig(check_invariants=True))
    yield h, eng
    if not h.gateway._stopped.is_set():
        h.stop()


def test_stream_parity_with_inprocess_generate(gw, model):
    """The core translation bar: tokens over the wire are EXACTLY the
    tokens ``generate()`` produces in-process — the wire is a
    transport, never a sampler."""
    h, _eng = gw
    prompts = {70: [9, 10, 11, 12], 71: [20, 21, 22]}
    res = {u: http_completion(h.host, h.port,
                              {"uid": u, "prompt": p, "max_tokens": 5,
                               "stream": True})
           for u, p in prompts.items()}
    ref_eng, _ = build_engine(model=model)
    ref = ref_eng.generate(prompts,
                           SamplingParams(max_new_tokens=5))
    for u in prompts:
        assert res[u]["code"] == 200
        assert res[u]["tokens"] == ref[u]
        assert res[u]["finish_reason"] == "length"


def test_driver_spans_pump_route_apply_in_order(gw):
    """The driver names its own share of a step (ds.gateway.*) on its
    backend's tracer: per step one pump (engine thread) and one route
    (event loop), in that order, each carrying how long its hand-over
    took; an apply (engine thread) only where the driver has something
    to tell the engine.  It echoes no token the engine sampled (the
    engine continues the stream itself), so the one apply of a stream
    that ends by its length is its flush."""
    h, eng = gw
    assert h.gateway.tracer is eng.tracer      # even while it is empty
    eng.tracer.clear()
    eng.tracer.enable()
    def gateway_events():
        return [e for e in eng.tracer.events()
                if e["name"].startswith("ds.gateway.")]
    try:
        r = http_completion(h.host, h.port, {"prompt": [3, 4, 5, 6],
                                             "max_tokens": 4,
                                             "stream": True})
        # the client has its last token as soon as the loop has routed
        # it; the engine thread's apply of that step (the one that
        # flushes the stream) comes after: wait for it before the tracer
        # goes off
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and not any(
                e["name"] == "ds.gateway.apply" and e["args"]["n_flush"]
                for e in gateway_events()):
            time.sleep(0.01)
    finally:
        eng.tracer.disable()
    assert r["code"] == 200 and len(r["tokens"]) == 4
    evs = gateway_events()
    order = [e["name"].rsplit(".", 1)[1] for e in evs]
    # every token-bearing pump is followed by its route
    busy = [i for i, e in enumerate(evs) if e["name"] == "ds.gateway.pump"
            and e["args"]["n_out"] > 0]
    assert len(busy) == 4
    for i in busy:
        assert order[i:i + 2] == ["pump", "route"], order
        pump, route = evs[i:i + 2]
        assert pump["ts_ns"] + pump["dur_ns"] <= route["ts_ns"]
        assert pump["args"]["queued_us"] >= 0.0
        assert route["args"]["wake_us"] >= 0.0
        assert route["args"]["n_tokens"] == pump["args"]["n_out"] == 1
    # the last token closes the stream: flushed, and nothing was fed back
    applies = [e for e in evs if e["name"] == "ds.gateway.apply"]
    assert [(e["args"]["n_put"], e["args"]["n_flush"])
            for e in applies] == [(0, 1)]
    assert order[busy[-1]:busy[-1] + 3] == ["pump", "route", "apply"]
    route, apply_ = evs[busy[-1] + 1:busy[-1] + 3]
    assert route["ts_ns"] + route["dur_ns"] <= apply_["ts_ns"]
    assert apply_["args"]["queued_us"] >= 0.0
    assert route["args"]["n_closed"] == 1
    # the engine's own phases sit inside the pumps, and the first pump
    # only launched (it had no token to hand over yet)
    pumps = [(e["ts_ns"], e["ts_ns"] + e["dur_ns"]) for e in evs
             if e["name"] == "ds.gateway.pump"]
    first = next(e for e in evs if e["name"] == "ds.gateway.pump")
    assert first["args"]["n_out"] == 0
    n_dispatch = 0
    for e in eng.tracer.events():
        if e["name"] in ("ds.serve.dispatch", "ds.serve.compile"):
            n_dispatch += 1
            assert any(a <= e["ts_ns"] and e["ts_ns"] + e["dur_ns"] <= b
                       for a, b in pumps)
    assert n_dispatch == 4


def test_gateway_without_a_backend_tracer_gets_its_own():
    from deepspeed_tpu.gateway.server import Gateway
    from deepspeed_tpu.telemetry import MetricsRegistry, SpanTracer

    class Stub:
        metrics = MetricsRegistry()

        def step(self, rng=None, sampling=None):
            return {7: 1}

        def _drain_reaped(self):
            return ()

    g = Gateway(Stub())
    assert isinstance(g.tracer, SpanTracer)
    g.tracer.enable()
    t = time.perf_counter()
    outs, reaped, t_end = g._pump(t_submit=t)
    g._apply([], [], t_submit=t_end)
    g._apply([], [])                          # a direct call: nothing queued
    assert outs == {7: 1} and reaped == {} and t_end >= t
    pump, a1, a2 = g.tracer.events()
    assert pump["name"] == "ds.gateway.pump"
    assert pump["args"]["n_out"] == 1 and pump["args"]["queued_us"] >= 0.0
    assert a1["args"]["queued_us"] >= 0.0 and a2["args"]["queued_us"] == 0.0
    g._exec.shutdown()


@pytest.mark.parametrize("fb,fl", [([(7, 5)], []), ([], [7]), ([], [])],
                         ids=["continuation", "flush", "nothing"])
def test_apply_then_pump_is_one_call_on_the_engine_thread(fb, fl):
    """The driver hands a routed step's continuations and flushes over
    WITH the next pump: the backend hears of them before it steps, in
    the caller's own call (no second hand-over through the loop), as an
    apply span and then a pump span; with nothing to apply it is a pump."""
    from deepspeed_tpu.gateway.server import Gateway, _Stream
    from deepspeed_tpu.telemetry import MetricsRegistry

    calls = []

    class Stub:
        metrics = MetricsRegistry()

        def put(self, uid, toks):
            calls.append(("put", uid, list(toks)))

        def flush(self, uid):
            calls.append(("flush", uid))

        def step(self, rng=None, sampling=None):
            calls.append(("step",))
            return {7: 1}

        def _drain_reaped(self):
            return ()

    g = Gateway(Stub())
    g._streams[7] = _Stream(uid=7, rid="r", max_tokens=8, want_stream=True,
                            queue=None)
    g.tracer.enable()
    t = time.perf_counter()
    outs, reaped, t_end = g._apply_then_pump(fb, fl, t_submit=t)
    assert outs == {7: 1} and reaped == {} and t_end >= t
    want = [("put", u, [tok]) for u, tok in fb] \
        + [("flush", u) for u in fl] + [("step",)]
    assert calls == want
    names = [e["name"] for e in g.tracer.events()]
    assert names == (["ds.gateway.apply"] if fb or fl else []) \
        + ["ds.gateway.pump"]
    evs = g.tracer.events()
    # the hand-over's stamp belongs to whichever span began the call
    assert evs[0]["args"]["queued_us"] > 0.0
    assert evs[-1]["args"]["queued_us"] == (0.0 if fb or fl else
                                            evs[0]["args"]["queued_us"])
    g._exec.shutdown()


def test_non_streaming_response(gw):
    h, _ = gw
    r = http_completion(h.host, h.port, {"prompt": [5, 6, 7],
                                         "max_tokens": 4})
    assert r["code"] == 200
    assert len(r["tokens"]) == 4
    assert r["finish_reason"] == "length"


def test_wire_journey_stamps(gw):
    h, _ = gw
    r = http_completion(h.host, h.port,
                        {"uid": 81, "prompt": [1, 2, 3],
                         "max_tokens": 2, "stream": True},
                        slo="interactive")
    assert r["code"] == 200
    j = h.gateway.wire_journey(81)
    phases = [s["phase"] for s in j]
    assert phases[:3] == ["received", "admitted", "sse_open"]
    assert "first_token" in phases and phases[-1] == "closed"
    assert j[0]["slo"] == "interactive"
    # stamps are monotone wire-relative ms
    times = [s["t_ms"] for s in j]
    assert times == sorted(times)


def test_wire_journeys_safe_during_live_streaming(gw):
    """Regression (tpulint v3 shared-state-race finding): wire_journey*
    read ``_journeys`` from the caller's thread while the event loop is
    stamping phases into it.  Unlocked, the snapshot comprehension can
    trip over a mid-mutation dict (RuntimeError: dictionary changed
    size during iteration) or see a half-built journey.  Hammer the
    readers while a stream is live: every snapshot must be coherent and
    the stream must finish untouched."""
    h, _ = gw
    done = threading.Event()
    res = {}

    def fire():
        res["r"] = http_completion(
            h.host, h.port,
            {"uid": 83, "prompt": [4, 5, 6], "max_tokens": 24,
             "stream": True})
        done.set()

    t = threading.Thread(target=fire)
    t.start()
    polls = 0
    while True:
        snap = h.gateway.wire_journeys()
        for j in snap.values():
            assert all("phase" in st and "t_ms" in st for st in j)
        h.gateway.wire_journey(83)
        polls += 1
        if done.is_set():
            break
    t.join()
    assert polls > 0
    assert res["r"]["code"] == 200
    assert len(res["r"]["tokens"]) == 24
    j = h.gateway.wire_journey(83)
    assert [s["phase"] for s in j][-1] == "closed"


def test_unknown_slo_class_is_400(gw):
    h, _ = gw
    r = http_completion(h.host, h.port, {"prompt": [1], "max_tokens": 1},
                        slo="platinum")
    assert r["code"] == 400


def test_uid_conflict_is_409(gw):
    h, _ = gw
    r1 = http_completion(h.host, h.port,
                         {"uid": 88, "prompt": [1, 2], "max_tokens": 2})
    assert r1["code"] == 200
    # 88 is now terminally finished on the engine: reusing it would
    # corrupt query()/journey identity, so the wire refuses
    r2 = http_completion(h.host, h.port,
                         {"uid": 88, "prompt": [1, 2], "max_tokens": 2})
    assert r2["code"] == 409


def test_concurrent_same_uid_exactly_one_admitted(gw, model):
    """The TOCTOU guard: the uid is RESERVED synchronously before any
    await, so two racing requests with the same uid can never both
    pass the 409 check — the loser's put would otherwise land as an
    engine 'continued' verdict and append its prompt onto the
    winner's."""
    import threading
    h, eng = gw
    out = []
    lock = threading.Lock()

    def fire():
        r = http_completion(h.host, h.port,
                            {"uid": 660, "prompt": [2, 7, 1, 8],
                             "max_tokens": 4, "stream": True})
        with lock:
            out.append(r)

    threads = [threading.Thread(target=fire, daemon=True)
               for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    codes = sorted(r["code"] for r in out)
    assert codes == [200, 409], codes
    winner = [r for r in out if r["code"] == 200][0]
    # the winner's stream is the uncorrupted 4-token prompt's output
    ref_eng, _ = build_engine(model=model)
    ref = ref_eng.generate({660: [2, 7, 1, 8]},
                           SamplingParams(max_new_tokens=4))
    assert winner["tokens"] == ref[660]


def test_malformed_content_length_is_400_not_500(gw):
    h, _ = gw
    for bad in (b"abc", b"-5"):
        sock = socket.create_connection((h.host, h.port), timeout=30)
        sock.sendall(b"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
                     b"Content-Length: " + bad + b"\r\n\r\n")
        line = sock.makefile("rb").readline()
        assert line.split()[1] == b"400", (bad, line)
        sock.close()


def test_unknown_route_404_and_wrong_method_405(gw):
    h, _ = gw
    code, _, _ = http_get(h.host, h.port, "/nope")
    assert code == 404
    code, _, _ = http_get(h.host, h.port, "/v1/completions")
    assert code == 405


def test_healthz_and_metrics_round_trip(gw):
    h, eng = gw
    code, _, body = http_get(h.host, h.port, "/healthz")
    assert code == 200
    payload = json.loads(body)
    assert payload["state"] in ("healthy", "degraded")
    assert payload["backend"]["state"] == payload["state"]
    code, headers, body = http_get(h.host, h.port, "/metrics")
    assert code == 200
    assert headers["content-type"].startswith("text/plain")
    # the existing Prometheus parser round-trips the exposition, and
    # one scrape carries BOTH engine counters and gateway counters
    metrics = parse_prometheus_text(body.decode())
    assert "serving_steps" in metrics or "serving_generated_tokens" \
        in metrics or any(k.startswith("serving_") for k in metrics)
    for name in ("serving_gateway_connections_total",
                 "serving_gateway_streams_total",
                 "serving_gateway_requests_total",
                 "serving_gateway_sse_bytes_total"):
        assert name in metrics, name
    reqs = metrics["serving_gateway_requests_total"]["samples"]
    by_route = {dict(labels).get("route"): v
                for (_n, labels), v in reqs.items()}
    assert by_route.get("completions", 0) >= 1
    assert by_route.get("healthz", 0) >= 1


def test_disconnect_mid_stream_cancels(gw):
    """Client vanishes mid-stream -> the engine-side ``cancel()``
    path fires: terminal status ``cancelled``, disconnect counter
    bumped, wire journey shows the disconnect."""
    h, eng = gw
    sock = socket.create_connection((h.host, h.port), timeout=30)
    body = json.dumps({"uid": 95, "prompt": [3, 4, 5],
                       "max_tokens": 40, "stream": True}).encode()
    sock.sendall((f"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
                  f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
    f = sock.makefile("rb")
    assert f.readline().split()[1] == b"200"
    got = 0
    while got < 2:
        line = f.readline().strip()
        if line.startswith(b"data: ") and b"[DONE]" not in line:
            if json.loads(line[6:])["choices"][0]["token"] is not None:
                got += 1
    sock.shutdown(socket.SHUT_RDWR)     # the makefile dups the fd:
    sock.close()                        # shutdown() is the disconnect
    f.close()
    deadline = time.perf_counter() + 20.0
    while time.perf_counter() < deadline:
        if eng.query(95)["status"] == "cancelled":
            break
        time.sleep(0.02)
    assert eng.query(95)["status"] == "cancelled"
    assert eng.metrics.get(
        "serving_gateway_disconnect_cancels_total").value() >= 1
    phases = [s["phase"] for s in h.gateway.wire_journey(95)]
    assert "disconnect" in phases


def test_saturation_sheds_429_with_retry_after(model):
    """A reject-policy engine under a flood: some requests shed at
    admission -> HTTP 429 with a computed integer Retry-After; the
    admitted ones still finish."""
    eng, _ = build_engine(
        OverloadConfig(max_queued_requests=1, shed_policy="reject"),
        model=model)
    h = spawn_gateway(eng, GatewayConfig())
    import threading
    out = {}
    lock = threading.Lock()

    def fire(i):
        r = http_completion(h.host, h.port,
                            {"prompt": list(range(1, 28)),
                             "max_tokens": 8, "stream": True})
        with lock:
            out[i] = r

    threads = [threading.Thread(target=fire, args=(i,), daemon=True)
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    h.stop()
    codes = [r["code"] for r in out.values()]
    assert 429 in codes, codes
    shed = [r for r in out.values() if r["code"] == 429]
    assert all(r["retry_after"] is not None and r["retry_after"] >= 1
               for r in shed)
    assert any(r["code"] == 200 and r["finish_reason"] == "length"
               for r in out.values())
    sheds = eng.metrics.get("serving_gateway_sheds_total")
    assert sheds.value(code="429") == len(shed)


def test_fleet_backed_gateway(model):
    """The same gateway fronts a FleetRouter unchanged: requests
    route+finish, /metrics serves the fleet's ONE merged exposition
    (replica labels + gateway counters), /healthz reflects fleet
    state."""
    router, _ = build_fleet(n_replicas=2, model=model)
    h = spawn_gateway(router, GatewayConfig())
    rs = [http_completion(h.host, h.port,
                          {"uid": 900 + i, "prompt": [11 + i, 12, 13],
                           "max_tokens": 3, "stream": True})
          for i in range(3)]
    assert all(r["code"] == 200 and len(r["tokens"]) == 3 for r in rs)
    code, _, body = http_get(h.host, h.port, "/metrics")
    assert code == 200
    text = body.decode()
    assert 'replica="r0"' in text and 'replica="r1"' in text
    assert "serving_gateway_connections_total" in text
    code, _, body = http_get(h.host, h.port, "/healthz")
    assert code == 200
    payload = json.loads(body)
    assert payload["state"] in ("healthy", "degraded")
    assert set(payload["backend"]["replicas"]) == {"r0", "r1"}
    # the ladder the gateway read is the router's own public seam,
    # mirroring engine.health_state()
    assert router.health_state() == payload["state"]
    # journeys carry the routed replica from the fleet verdict
    j = h.gateway.wire_journey(900)
    admitted = [s for s in j if s["phase"] == "admitted"][0]
    assert admitted["replica"] in ("r0", "r1")
    h.stop()


def test_drain_finishes_inflight_and_503s_late_arrivals(model):
    """The SIGTERM contract via the programmatic trigger the handler
    schedules: in-flight streams complete, late arrivals 503 with
    Retry-After, the backend drain snapshot lands, exit is clean.

    The stream in flight is held two tokens in (the engine's ``step``
    hands the gateway empty rounds: the engine thread and the event
    loop go on answering) until the late arrival has its answer.  Left
    to run, its six tokens are a few milliseconds: on a loaded host the
    drain was through and the listener closed before this thread had
    sent the late request, which then met a refused connection, not a
    503."""
    import threading
    eng, _ = build_engine(model=model)
    h = spawn_gateway(eng, GatewayConfig())
    # warm so "in-flight" means decoding, not compiling
    http_completion(h.host, h.port, {"prompt": [1, 2], "max_tokens": 1})
    box, held = {}, []
    answered = threading.Event()
    step = eng.step

    def gated(*a, **k):
        seq = eng.state.seqs.get(700)
        if not answered.is_set() and seq is not None \
                and len(seq.tokens) >= 2:
            held.append(1)
            return {}
        return step(*a, **k)

    eng.step = gated

    def drive():
        box["r"] = http_completion(
            h.host, h.port, {"uid": 700, "prompt": [7, 8, 9],
                             "max_tokens": 6, "stream": True})

    t = threading.Thread(target=drive, daemon=True)
    t.start()
    deadline = time.perf_counter() + 30.0
    while time.perf_counter() < deadline:
        if held:            # two tokens in and held there: in flight
            break
        time.sleep(0.01)
    h.begin_drain(deadline_ms=60_000.0)
    while not h.gateway._draining \
            and time.perf_counter() < deadline:
        time.sleep(0.005)
    try:
        late = http_completion(h.host, h.port,
                               {"prompt": [1], "max_tokens": 1})
    finally:
        answered.set()
    t.join(60)
    assert held, "the stream was not in flight when the late one came"
    assert late["code"] == 503 and late["retry_after"] >= 1
    assert box["r"]["finish_reason"] == "length"
    assert len(box["r"]["tokens"]) == 6
    h._thread.join(60)
    assert not h._thread.is_alive()
    assert h.gateway.final_snapshot is not None
    assert eng.request_metrics()["aggregate"]["open"] == 0


def test_refuses_to_start_on_dead_engine(model):
    """The small-fix satellite: a dead backend is refused LOUDLY at
    start — accepting-then-shedding 100% would hide the outage."""
    eng, _ = build_engine(model=model)
    eng._health = "dead"
    with pytest.raises(GatewayError, match="DEAD"):
        spawn_gateway(eng, GatewayConfig())


# --------------------------------------------------------------------------
# the ops plane: /debug/* gating, token auth, budgets
# (docs/OBSERVABILITY.md "SLOs & error budgets")
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def ops_gw(model, tmp_path_factory):
    """A gateway with the ops plane ON and a token configured, over an
    SLO-tracking engine with a flight dir (dump + capture budgets are
    real)."""
    from deepspeed_tpu.inference import FailureConfig

    d = tmp_path_factory.mktemp("ops_plane")
    eng, _ = build_engine(model=model, slo="on", anomaly="on",
                          failure=FailureConfig(flight_dir=str(d)))
    h = spawn_gateway(eng, GatewayConfig(ops="on", ops_token="s3cret"))
    yield h, eng
    h.stop()


def _post(h, path, token=None):
    from tools.loadgen import http_post
    headers = {"x-ops-token": token} if token is not None else {}
    return http_post(h.host, h.port, path, headers=headers)


def test_ops_default_off_whole_surface_404s(gw):
    """ops='auto' resolves OFF: every /debug/* path — reads AND
    mutators, known and unknown — 404s exactly like an absent route
    (no probe-able difference)."""
    h, _ = gw
    for path in ("/debug/slo", "/debug/anomalies", "/debug/config",
                 "/debug/journeys/1", "/debug/nope"):
        code, _, body = http_get(h.host, h.port, path)
        assert code == 404, (path, code)
        assert json.loads(body)["error"]["code"] == "not_found"
    from tools.loadgen import http_post
    code, _, body = http_post(h.host, h.port, "/debug/dump",
                              headers={"x-ops-token": "anything"})
    assert code == 404
    assert json.loads(body)["error"]["code"] == "not_found"


def test_ops_invalid_value_rejected(model):
    eng, _ = build_engine(model=model)
    with pytest.raises(GatewayError, match="ops="):
        spawn_gateway(eng, GatewayConfig(ops="sometimes"))


def test_ops_unknown_debug_route_404(ops_gw):
    h, _ = ops_gw
    code, _, body = http_get(h.host, h.port, "/debug/nope")
    assert code == 404
    assert json.loads(body)["error"]["code"] == "not_found"


def test_ops_wrong_method_405(ops_gw):
    h, _ = ops_gw
    code, _, body = _post(h, "/debug/slo", token="s3cret")
    assert code == 405
    code, _, body = http_get(h.host, h.port, "/debug/dump")
    assert code == 405
    assert json.loads(body)["error"]["code"] == "method_not_allowed"


def test_ops_mutator_auth_ladder(ops_gw):
    """Missing header -> 401; wrong token -> 403; both refused BEFORE
    any backend touch."""
    h, _ = ops_gw
    code, _, body = _post(h, "/debug/dump")
    assert code == 401
    assert json.loads(body)["error"]["code"] == "missing_ops_token"
    code, _, body = _post(h, "/debug/capture", token="wrong")
    assert code == 403
    assert json.loads(body)["error"]["code"] == "bad_ops_token"


def test_ops_mutators_disabled_without_configured_token(model):
    """ops='on' with no ops_token: reads serve, mutators are 403 even
    with a (necessarily wrong) token — a deployment opts into remote
    dump/capture explicitly."""
    eng, _ = build_engine(model=model, slo="on")
    h = spawn_gateway(eng, GatewayConfig(ops="on"))
    try:
        code, _, _ = http_get(h.host, h.port, "/debug/slo")
        assert code == 200
        code, _, body = _post(h, "/debug/dump", token="guess")
        assert code == 403
        assert json.loads(body)["error"]["code"] == \
            "ops_mutations_disabled"
    finally:
        h.stop()


def test_ops_slo_scorecard_matches_backend(ops_gw):
    h, eng = ops_gw
    http_completion(h.host, h.port, {"prompt": [3, 4, 5],
                                     "max_tokens": 2}, slo="interactive")
    code, _, body = http_get(h.host, h.port, "/debug/slo")
    assert code == 200
    assert json.loads(body) == json.loads(
        json.dumps(eng.slo_scorecard()))
    assert json.loads(body)["enabled"] is True


def test_ops_journey_routes(ops_gw):
    h, _ = ops_gw
    r = http_completion(h.host, h.port, {"uid": 4100,
                                         "prompt": [9, 8, 7],
                                         "max_tokens": 2})
    assert r["code"] == 200
    code, _, body = http_get(h.host, h.port, "/debug/journeys/4100")
    assert code == 200
    j = json.loads(body)
    phases = [e["phase"] for e in j["wire"]]
    assert phases[0] == "received" and "closed" in phases
    assert j["fleet"] is None          # engine backend: no fleet leg
    code, _, body = http_get(h.host, h.port, "/debug/journeys/abc")
    assert code == 400
    assert json.loads(body)["error"]["code"] == "bad_uid"
    code, _, body = http_get(h.host, h.port, "/debug/journeys/999999")
    assert code == 404
    assert json.loads(body)["error"]["code"] == "unknown_uid"


def test_ops_anomalies_and_config(ops_gw):
    h, eng = ops_gw
    code, _, body = http_get(h.host, h.port, "/debug/anomalies")
    assert code == 200
    summ = json.loads(body)
    assert summ["enabled"] is True and "by_signal" in summ
    code, _, body = http_get(h.host, h.port, "/debug/config")
    assert code == 200
    cfgd = json.loads(body)
    assert cfgd["fingerprint"]
    # the secret never round-trips over the surface it guards
    assert cfgd["gateway"]["ops_token"] == "<set>"
    assert "s3cret" not in body.decode("utf-8")
    assert cfgd["backend"]["slo"] == "on"


def test_ops_anomaly_tail_closes_deterministically(ops_gw):
    h, _ = ops_gw
    code, headers, body = http_get(h.host, h.port,
                                   "/debug/anomalies?tail=0")
    assert code == 200
    assert headers["content-type"].startswith("text/event-stream")
    assert body == protocol.SSE_DONE
    code, _, body = http_get(h.host, h.port,
                             "/debug/anomalies?tail=x")
    assert code == 400
    assert json.loads(body)["error"]["code"] == "bad_tail"


def test_ops_mutators_respect_budgets(ops_gw):
    """POST /debug/dump writes one bundle; POST /debug/capture arms one
    window and a second POST while it is armed reports ok=False — a
    wire client can never open an unbounded window."""
    h, eng = ops_gw
    code, _, body = _post(h, "/debug/dump", token="s3cret")
    assert code == 200
    d = json.loads(body)
    assert d["ok"] is True and d["dump"]
    code, _, body = _post(h, "/debug/capture", token="s3cret")
    assert code == 200
    first = json.loads(body)
    assert first["ok"] is True and first["capture"]
    code, _, body = _post(h, "/debug/capture", token="s3cret")
    assert code == 200
    assert json.loads(body) == {"ok": False, "capture": None}

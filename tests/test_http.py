"""The HTTP serving front-end (docs/http.md): golden-byte SSE framing,
completions JSON schema, request parsing, Prometheus rendering — then
live-server tests over a real socket (MockEngine replicas: streaming,
429-on-full, disconnect-mid-stream -> abort) and real-engine e2e
(bit-exactness vs direct generate(), block reclamation, the
abort-inside-fork-spawn-window regression)."""
import http.client
import json
import time

import jax
import pytest

from repro.configs import get_config
from repro.core.engine import EngineConfig, SiPipeEngine
from repro.core.sampling_params import SamplingParams
from repro.models import ModelOptions, ShardCtx, build_model
from repro.serving import protocol as proto
from repro.serving.mock import MockEngine
from repro.serving.protocol import ProtocolError
from repro.serving.router import EngineReplica, Router
from repro.serving.server import CompletionServer


# ---------------------------------------------------------------------------
# Golden bytes: SSE framing is part of the wire contract
# ---------------------------------------------------------------------------

def test_sse_chunk_golden_bytes():
    chunk = proto.completion_chunk(7, 1234, "m", 0, [3, 4])
    assert proto.sse_event(chunk) == (
        b'data: {"choices":[{"finish_reason":null,"index":0,"logprobs":null,'
        b'"text":"3 4","token_ids":[3,4]}],"created":1234,"id":"cmpl-7",'
        b'"model":"m","object":"text_completion.chunk"}\n\n')


def test_sse_terminal_chunk_golden_bytes():
    chunk = proto.completion_chunk(7, 1234, "m", 1, [], "length")
    assert proto.sse_event(chunk) == (
        b'data: {"choices":[{"finish_reason":"length","index":1,'
        b'"logprobs":null,"text":"","token_ids":[]}],"created":1234,'
        b'"id":"cmpl-7","model":"m","object":"text_completion.chunk"}\n\n')
    assert proto.SSE_DONE == b"data: [DONE]\n\n"


def test_completion_response_schema_and_usage():
    resp = proto.completion_response(
        9, 1234, "m",
        [{"token_ids": [5, 6, 7], "finish_reason": "length"},
         {"token_ids": [8], "finish_reason": "stop"}],
        prompt_tokens=4)
    assert resp["id"] == "cmpl-9" and resp["object"] == "text_completion"
    assert [c["index"] for c in resp["choices"]] == [0, 1]
    assert resp["choices"][0]["text"] == "5 6 7"
    assert resp["choices"][1]["finish_reason"] == "stop"
    assert resp["usage"] == {"prompt_tokens": 4, "completion_tokens": 4,
                             "total_tokens": 8}


# ---------------------------------------------------------------------------
# Request parsing
# ---------------------------------------------------------------------------

def test_parse_accepts_token_ids_and_strings():
    r = proto.parse_completion_request({"prompt": [3, 5, 7]}, 64)
    assert r.prompt_ids == [3, 5, 7] and r.tenant == "anonymous"
    r2 = proto.parse_completion_request({"prompt": "hi"}, 64)
    assert r2.prompt_ids == [2 + (b % 62) for b in b"hi"]


@pytest.mark.parametrize("body,match", [
    ({}, "prompt"),
    ({"prompt": []}, "prompt"),
    ({"prompt": [999]}, "out of range"),
    ({"prompt": [1], "max_tokens": 0}, "max_tokens"),
    ({"prompt": [1], "max_tokens": "4"}, "max_tokens"),
    ({"prompt": [1], "n": 0}, "n must"),
    ({"prompt": [1], "n": True}, "n"),          # bool is not an int here
    ({"prompt": [1], "temperature": -1.0}, "temperature"),
    ({"prompt": [1], "top_p": 0.0}, "top_p"),
    ({"prompt": [1], "stream": 1}, "stream"),
])
def test_parse_rejects_malformed(body, match):
    with pytest.raises(ProtocolError, match=match):
        proto.parse_completion_request(body, 64)


def test_parse_greedy_and_priority_thread_into_params():
    r = proto.parse_completion_request(
        {"prompt": [1], "temperature": 0.0, "priority": 3,
         "max_tokens": 5}, 64)
    p = r.sampling_params()
    assert p.greedy and p.priority == 3 and p.max_new_tokens == 5


def test_parse_tenant_precedence_and_cap():
    body = {"prompt": [1], "user": "body-user", "max_tokens": 100}
    assert proto.parse_completion_request(body, 64).tenant == "body-user"
    r = proto.parse_completion_request(body, 64, tenant="key-9",
                                       max_tokens_cap=8)
    assert r.tenant == "key-9" and r.max_tokens == 8


def test_render_prometheus_labels_and_filtering():
    text = proto.render_prometheus(
        {"r0": {"a": 1, "flag": True, "nested": {"x": 1}, "f": 2.5}},
        {"c": 3})
    assert text == ('repro_a{replica="r0"} 1\n'
                    'repro_f{replica="r0"} 2.5\n'
                    'repro_c 3\n')


# ---------------------------------------------------------------------------
# Live server over MockEngine replicas
# ---------------------------------------------------------------------------

def _server(**kw):
    reps = [EngineReplica("r0", MockEngine())]
    srv = CompletionServer(Router(reps), vocab_size=64, model_name="mock",
                           **kw).start()
    return srv, reps[0].engine


def _request(addr, body=None, method="POST", path="/v1/completions",
             headers=None, timeout=30.0):
    conn = http.client.HTTPConnection(*addr, timeout=timeout)
    conn.request(method, path, json.dumps(body) if body is not None else None,
                 {"Content-Type": "application/json", **(headers or {})})
    return conn, conn.getresponse()


def _read_sse(resp):
    events, done = [], False
    while True:
        line = resp.readline()
        if not line:
            break
        if line == b"\n":
            continue
        assert line.startswith(b"data: "), line
        payload = line[len(b"data: "):].rstrip(b"\n")
        if payload == b"[DONE]":
            done = True
            break
        events.append(json.loads(payload))
    return events, done


def test_http_streamed_completion_over_the_wire():
    srv, eng = _server()
    try:
        conn, resp = _request(srv.address, {
            "prompt": [3, 5], "max_tokens": 4, "stream": True})
        assert resp.status == 200
        assert resp.headers["Content-Type"] == "text/event-stream"
        events, done = _read_sse(resp)
        conn.close()
        assert done
        toks = [t for e in events for c in e["choices"]
                for t in c["token_ids"] if c["index"] == 0]
        assert toks == [(8 + k) % 64 for k in range(4)]
        finals = [c for e in events for c in e["choices"]
                  if c["finish_reason"]]
        assert [c["finish_reason"] for c in finals] == ["length"]
        assert all(e["id"].startswith("cmpl-") for e in events)
    finally:
        srv.close()


def test_http_nonstream_aggregates_with_usage():
    srv, eng = _server()
    try:
        conn, resp = _request(srv.address, {
            "prompt": [3, 5], "max_tokens": 4, "n": 2, "stream": False})
        assert resp.status == 200
        out = json.loads(resp.read())
        conn.close()
        assert out["object"] == "text_completion"
        assert len(out["choices"]) == 2
        assert out["choices"][0]["token_ids"] == [(8 + k) % 64
                                                  for k in range(4)]
        assert out["choices"][1]["token_ids"] == [(8 + 31 + k) % 64
                                                  for k in range(4)]
        assert all(c["finish_reason"] == "length" for c in out["choices"])
        assert out["usage"] == {"prompt_tokens": 2, "completion_tokens": 8,
                                "total_tokens": 10}
    finally:
        srv.close()


def test_http_429_when_queue_full():
    srv, eng = _server(max_queue=0)
    try:
        conn, resp = _request(srv.address, {"prompt": [3], "max_tokens": 2})
        assert resp.status == 429
        assert resp.headers["Retry-After"] == "1"
        err = json.loads(resp.read())
        conn.close()
        assert err["error"]["code"] == 429
        assert eng.n_steps == 0           # rejected before any engine work
    finally:
        srv.close()


def test_http_400_and_404():
    srv, _ = _server()
    try:
        conn = http.client.HTTPConnection(*srv.address, timeout=10)
        conn.request("POST", "/v1/completions", b"{not json",
                     {"Content-Type": "application/json"})
        assert conn.getresponse().status == 400
        conn.close()
        conn, resp = _request(srv.address, {"prompt": [1]},
                              path="/v1/nonesuch")
        assert resp.status == 404
        conn.close()
        conn, resp = _request(srv.address, {"max_tokens": 2})
        assert resp.status == 400
        body = json.loads(resp.read())
        conn.close()
        assert "prompt" in body["error"]["message"]
    finally:
        srv.close()


def test_http_health_models_metrics():
    srv, _ = _server()
    try:
        conn, resp = _request(srv.address, method="GET", path="/health")
        assert resp.status == 200
        h = json.loads(resp.read())
        conn.close()
        assert h["status"] == "ok" and h["replicas"]["r0"]["healthy"]

        conn, resp = _request(srv.address, method="GET", path="/v1/models")
        models = json.loads(resp.read())
        conn.close()
        assert models["data"][0]["id"] == "mock"

        conn, resp = _request(srv.address, method="GET", path="/metrics")
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.read().decode()
        conn.close()
        assert 'repro_kv_blocks_total{replica="r0"} 64' in text
        assert "repro_admission_admitted_total 0" in text
        assert "repro_http_disconnects_total 0" in text
    finally:
        srv.close()


def test_http_disconnect_mid_stream_aborts_and_reclaims():
    srv, eng = _server()
    try:
        conn, resp = _request(srv.address, {
            "prompt": [3], "max_tokens": 100_000, "stream": True})
        assert resp.status == 200
        first = resp.readline()           # one event, then walk away
        assert first.startswith(b"data: ")
        # the response's makefile holds the socket fd: close BOTH, or no
        # FIN/RST ever reaches the server and it can't see us leave
        resp.close()
        conn.close()
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            if (eng.n_aborts == 1
                    and eng.load()["kv_blocks_free"] == eng.kv_blocks):
                break
            time.sleep(0.01)
        assert eng.n_aborts == 1
        assert eng.load()["kv_blocks_free"] == eng.kv_blocks
        assert srv.n_disconnects == 1
    finally:
        srv.close()


def test_http_close_rejects_new_requests():
    srv, _ = _server()
    srv.admission.close()                 # draining: listener still up
    try:
        conn, resp = _request(srv.address, {"prompt": [1]}, timeout=10.0)
        assert resp.status == 503
        assert "draining" in json.loads(resp.read())["error"]["message"]
        conn.close()
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Real-engine e2e (slow): parity, reclamation, fork-window abort
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model_and_params():
    cfg = get_config("stablelm-1.6b-smoke")
    model = build_model(cfg, ShardCtx.single(), ModelOptions())
    return cfg, model, model.init(jax.random.key(0))


def _paged_engine(model, params, **kw):
    return SiPipeEngine(model, params, EngineConfig(
        pp_degree=2, max_batch=2, max_seq_len=64, n_samplers=2,
        kv_layout="paged", kv_block_size=8, **kw))


def _http_over(cfg, model, params, **kw):
    from repro.launch.serve import build_http_server
    _, srv = build_http_server(
        "stablelm-1.6b-smoke", pp=2, max_batch=2, max_seq_len=64,
        kv_layout="paged", block_size=8,
        prebuilt=(cfg, model, params), **kw)
    return srv.start()


@pytest.mark.slow
def test_http_greedy_tokens_bit_identical_to_direct_generate(
        model_and_params):
    """The transport adds nothing: greedy tokens streamed over HTTP are
    the same bytes a direct engine.generate() call produces."""
    cfg, model, params = model_and_params
    prompts = [[5, 9, 13, 17, 21], [7, 11, 2]]
    sp = SamplingParams(greedy=True, max_new_tokens=8)
    eng = _paged_engine(model, params)
    direct = {}
    for out in eng.generate(prompts, sp):
        if out.finished:
            direct[out.request_id] = out.token_ids.to_list()
    eng.shutdown()
    ref = [direct[k] for k in sorted(direct)]

    srv = _http_over(cfg, model, params)
    try:
        got = []
        for p in prompts:
            conn, resp = _request(srv.address, {
                "prompt": p, "max_tokens": 8, "temperature": 0.0,
                "stream": True}, timeout=120.0)
            assert resp.status == 200
            events, done = _read_sse(resp)
            conn.close()
            assert done
            got.append([t for e in events for c in e["choices"]
                        for t in c["token_ids"]])
        assert got == ref
    finally:
        srv.close()


@pytest.mark.slow
def test_http_disconnect_reclaims_real_engine_blocks(model_and_params):
    cfg, model, params = model_and_params
    srv = _http_over(cfg, model, params)
    eng = srv.router.replicas[0].engine
    try:
        conn, resp = _request(srv.address, {
            "prompt": [5, 9, 13], "max_tokens": 50, "temperature": 0.0,
            "stream": True}, timeout=120.0)
        assert resp.status == 200
        assert resp.readline().startswith(b"data: ")
        resp.close()                      # mid-stream disconnect (both
        conn.close()                      # handles share the socket fd)
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            snap = eng.load()
            if (snap["active_requests"] == 0
                    and snap["kv_blocks_free"] == snap["kv_blocks_total"]):
                break
            time.sleep(0.05)
        snap = eng.load()
        assert snap["active_requests"] == 0
        assert snap["kv_blocks_free"] == snap["kv_blocks_total"]
    finally:
        srv.close()


@pytest.mark.slow
def test_abort_inside_fork_spawn_window_leaks_nothing(model_and_params):
    """Satellite regression: abort landing BETWEEN the scheduler spawning
    fork children (first token) and the engine attaching them to the
    Request must still reclaim every block — the children live only in
    scheduler state in that window."""
    cfg, model, params = model_and_params
    eng = _paged_engine(model, params)
    hold = {"on": True}
    real_attach = eng._attach_forks
    eng._attach_forks = lambda: None if hold["on"] else real_attach()
    rid = eng.add_request([5, 9, 13, 17],
                          SamplingParams(greedy=True, max_new_tokens=12, n=3))
    for _ in range(10_000):
        eng.step()
        if eng.scheduler.fork_children_of(rid):
            break
    assert eng.scheduler.fork_children_of(rid), "forks never spawned"
    assert eng.requests[rid].forks == []      # the attach window is open
    assert eng.abort(rid)
    hold["on"] = False                        # attach path back to normal
    for _ in range(10_000):
        if not eng.has_work:
            break
        eng.step()
    eng.shutdown()
    m = eng.metrics()
    assert not eng.has_work
    assert m["kv_blocks_free"] == m["kv_blocks_total"]


def test_http_metrics_show_stage_waits_of_a_real_engine(model_and_params):
    """An operator's scrape carries each stage's wait and copy counters
    and the driver's gate wait (docs/http.md)."""
    cfg, model, params = model_and_params
    srv = _http_over(cfg, model, params)
    try:
        conn, resp = _request(srv.address, {
            "prompt": [5, 9, 13], "max_tokens": 6, "temperature": 0.0},
            timeout=120.0)
        assert resp.status == 200
        resp.read()
        conn.close()
        conn, resp = _request(srv.address, method="GET", path="/metrics")
        text = resp.read().decode()
        conn.close()
        gauges = dict(ln.rsplit(" ", 1) for ln in text.splitlines())
        for i in (0, 1):
            for key in ("input_wait_s", "recv_wait_s", "d2h_s",
                        "decode_steps", "decode_tokens", "chunk_steps"):
                assert f'repro_stage{i}_{key}{{replica="r0"}}' in gauges
        assert float(gauges['repro_gate_wait_s{replica="r0"}']) > 0
        assert float(gauges['repro_stage1_recv_wait_s{replica="r0"}']) > 0
        assert float(gauges['repro_stage1_decode_sampled{replica="r0"}']) > 0
    finally:
        srv.close()

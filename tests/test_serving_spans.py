"""Host spans of the served query path (``verdict.*``): a small front over
HTTP with four concurrent clients, traced by ``jax.profiler``. Every span
appears on the path it covers, nested as the program nests them, and one
request's id joins its request, prescreen and the flush that answered it.
The profiler may write the spans of several threads on one host line, so
nesting is checked by time and joined by the ids the spans carry."""
import json
import threading
import urllib.request

import jax
import pytest
from jax.profiler import ProfileData

from repro.aqp import workload as W
from repro.core.engine import EngineConfig
from repro.serving.front import ServingFront, TenantSpec, serve_http
from repro.verdict.session import ErrorBudget

SPANS = ("request", "encode", "prescreen", "flush", "plan", "discover",
         "gather", "scan", "estimates", "replay", "improve", "ingest",
         "resolve")
# child -> the span it runs inside
PARENT = {"plan": "flush", "replay": "flush", "resolve": "flush",
          "discover": "plan", "gather": "replay", "scan": "replay",
          "estimates": "replay", "improve": "replay", "flush": "request"}


def _query(i):
    lo = 0.5 + 0.4 * i
    q = {"aggs": [{"kind": "avg", "measure": "v0"}],
         "where": [{"op": "between", "column": "x0", "lo": lo,
                    "hi": lo + 4.0}]}
    if i % 2:
        q["group_by"] = ["c0"]
    return q


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    rel = W.make_relation(seed=0, n_rows=3_000, n_num=2, cat_sizes=(4,),
                          n_measures=1, lengthscale=0.4, noise=0.2)
    front = ServingFront(rel, EngineConfig(sample_rate=0.2, n_batches=4,
                                           capacity=128, seed=0))
    front.add_tenant(TenantSpec("t", budget=ErrorBudget(
        target_rel_error=0.05)))
    server = serve_http(front)
    host, port = server.server_address
    url = f"http://{host}:{port}/v1/tenants/t/execute"
    replies = []

    def client(c):
        for k in range(2):
            body = json.dumps({"query": _query(4 * k + c)}).encode()
            req = urllib.request.Request(
                url, data=body, headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                replies.append(json.loads(r.read()))

    tdir = tmp_path_factory.mktemp("trace")
    try:
        with jax.profiler.trace(str(tdir)):
            threads = [threading.Thread(target=client, args=(c,))
                       for c in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            front.tenant("t").service.drain()  # ingest lands in the trace
    finally:
        server.shutdown()
        server.server_close()
    assert not any(t.is_alive() for t in threads)
    assert len(replies) == 8 and all(r["kind"] == "answer" for r in replies)
    path = sorted(tdir.rglob("*.xplane.pb"))[-1]
    spans = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("verdict."):
                    start = int(ev.start_ns)
                    spans.setdefault(ev.name[len("verdict."):], []).append(
                        (start, start + int(ev.duration_ns), dict(ev.stats)))
    return spans, front


def _inside(child, parent):
    return parent[0] <= child[0] and child[1] <= parent[1]


def test_every_span_appears(traced):
    spans, _ = traced
    assert set(spans) == set(SPANS)
    assert len(spans["request"]) == 8
    assert len(spans["encode"]) == 8
    assert len(spans["prescreen"]) == 8


@pytest.mark.parametrize("child", sorted(PARENT))
def test_spans_nest_as_the_program_does(traced, child):
    spans, _ = traced
    for c in spans[child]:
        assert any(_inside(c, p) for p in spans[PARENT[child]]), (child, c)


def test_a_request_id_joins_its_request_prescreen_encode_and_flush(traced):
    spans, front = traced
    requests = {s[2]["req"]: s for s in spans["request"]}
    assert len(requests) == 8  # one id per request
    for name in ("prescreen", "encode"):
        for s in spans[name]:
            assert _inside(s, requests[s[2]["req"]]), (name, s)
    answered = []
    for f in spans["flush"]:
        reqs = [int(r) for r in str(f[2]["reqs"]).split()]
        assert len(reqs) == f[2]["queries"]
        for r in reqs:
            # the flush that answered a request began while it was open
            assert requests[r][0] <= f[0] <= requests[r][1]
        answered += reqs
    assert sorted(answered) == sorted(requests)
    svc = front.tenant("t").stats()["service"]
    assert svc["flushes"] == len(spans["flush"])
    assert svc["flushed_queries"] == 8
    assert svc["prescreen_lookups"] == 8
    assert [f[2]["flush"] for f in sorted(spans["flush"])] == list(
        range(1, len(spans["flush"]) + 1))


def test_a_gather_counts_the_rows_it_takes(traced):
    spans, front = traced
    batches = front.tenant("t").session.engine.batches
    sizes = {len(r) for r in batches.batch_rows}
    assert {s[2]["rows"] for s in spans["gather"]} <= sizes

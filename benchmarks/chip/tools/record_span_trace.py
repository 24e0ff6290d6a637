"""Record the small TPU trace with the program's spans that the span
reduction's tests read.

    python3 benchmarks/chip/tools/record_span_trace.py <out_dir>

Serves a small relation (learning off, the fused scan compiled for the
chip) over HTTP to four concurrent clients, one fresh AVG range query
each, after microbatches of every size up to four have warmed each shape.
Traces that with the JAX profiler (Python tracer off, to keep the file
small), writes the ``.xplane.pb`` xz-compressed to
``<out_dir>/tiny_spans_tpu.xplane.pb.xz`` and prints the span tables
``chipbench.spans.reduce`` makes of it.
"""
import json
import lzma
import sys
import tempfile
import threading
import urllib.request
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

CLIENTS = 4


def _query(i):
    lo = 0.5 + 0.01 * i  # distinct queries: no answer-cache hits
    return {"aggs": [{"kind": "avg", "measure": "v0"}],
            "where": [{"op": "between", "column": "x0", "lo": lo,
                       "hi": lo + 4.0}]}


def _post(url, query):
    body = json.dumps({"query": query}).encode()
    req = urllib.request.Request(url, data=body,
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as r:
        return json.loads(r.read())


def record(out: Path) -> Path:
    import jax

    from repro.aqp import workload as W
    from repro.core.engine import EngineConfig
    from repro.serving.front import ServingFront, TenantSpec, serve_http
    from repro.serving.front.wire import query_from_json
    from repro.verdict.session import ErrorBudget

    rel = W.make_relation(seed=0, n_rows=20_000, n_num=2, cat_sizes=(4,),
                          n_measures=1, lengthscale=0.4, noise=0.2)
    front = ServingFront(rel, EngineConfig(
        sample_rate=0.2, n_batches=4, capacity=128, seed=0, learning=False,
        use_kernels=True))
    budget = ErrorBudget(target_rel_error=0.05)
    session = front.add_tenant(TenantSpec("t", budget=budget)).session
    for n in range(CLIENTS, 0, -1):  # every microbatch size the trace sees
        session.execute_many([query_from_json(session.schema, _query(100 + i))
                              for i in range(n)], budget=budget)
    server = serve_http(front)
    host, port = server.server_address
    url = f"http://{host}:{port}/v1/tenants/t/execute"
    try:
        _post(url, _query(200))  # the HTTP path
        replies = []

        def client(c):
            replies.append(_post(url, _query(c)))

        tdir = tempfile.mkdtemp()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(tdir, profiler_options=options)
        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(CLIENTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        jax.profiler.stop_trace()
    finally:
        server.shutdown()
        server.server_close()
    if any(t.is_alive() for t in threads) or len(replies) != CLIENTS:
        raise RuntimeError("a client did not finish")
    src = sorted(Path(tdir).rglob("*.xplane.pb"))[-1]
    out.mkdir(parents=True, exist_ok=True)
    dst = out / "tiny_spans_tpu.xplane.pb.xz"
    dst.write_bytes(lzma.compress(src.read_bytes(),
                                  preset=9 | lzma.PRESET_EXTREME))
    return dst


def main(argv):
    import jax

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    from jax.profiler import ProfileData

    from chipbench import spans, tracing

    dst = record(Path(argv[0]))
    profile = ProfileData.from_serialized_xspace(
        lzma.decompress(dst.read_bytes()))
    print("bytes", dst.stat().st_size)
    for plane in profile.planes:
        if plane.name == tracing.HOST_PLANE:
            for line in plane.lines:
                names = sorted({e.name for e in line.events
                                if e.name.startswith(spans.PREFIX)})
                print("  line", repr(line.name), names)
    print(json.dumps(spans.reduce(profile, 1.0), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""Scheduler latency under load: filter/bind p50/p99 over the REAL HTTP
extender protocol against a synthetic fleet (default 100 nodes x 1,000 pods).

Parity: the reference tracks extender Filter/Bind latency via its
Prometheus histograms (pkg/scheduler/routes + BASELINE.md "Bind p99" row);
this publishes the vTPU numbers the same way: client-observed wall times for
the percentiles, corroborated by the product's own
vtpu_scheduler_{filter,bind}_seconds histograms.

r3 additions: --patch-rtt-ms injects an emulated
apiserver write RTT into the fake client, and --concurrency drives that many
filter/bind pipelines at once — together they prove the filter's decision
PATCH happens outside the global filter lock (a 5 ms RTT inside the lock
would cap the whole scheduler at ~200 filters/s no matter the concurrency).

Usage:  python benchmarks/sched_bench.py [--nodes 100] [--pods 1000]
            [--patch-rtt-ms 5] [--concurrency 8]
Emits:  one JSON object on stdout (written to SCHEDLAT.json by the caller).
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import threading
import time
import urllib.request

from vtpu.device import codec
from vtpu.device.tpu.device import TpuConfig, TpuDevices
from vtpu.device.tpu.topology import default_ici_mesh
from vtpu.device.types import DeviceInfo
from vtpu.device.registry import register_backend
from vtpu.scheduler.routes import SchedulerServer
from vtpu.util import nodelock
from vtpu.scheduler.scheduler import Scheduler
from vtpu.scheduler.webhook import WebHook
from vtpu.util.k8sclient import FakeKubeClient

REGISTER_ANNO = "vtpu.io/node-tpu-register"


def _devices(node: str, n_chips: int) -> list[DeviceInfo]:
    mesh = default_ici_mesh(n_chips)
    return [
        DeviceInfo(
            id=f"{node}-tpu-{i}", count=4, devmem=16384, devcore=100,
            type="TPU-v5e", numa=0 if i < n_chips // 2 else 1,
            ici=mesh[i], index=i,
        )
        for i in range(n_chips)
    ]


def _pod(i: int) -> dict:
    # mixed fractional asks, the shared-chip workload the scheduler is for
    mem = (1024, 2048, 4096)[i % 3]
    return {
        "metadata": {"name": f"bench-{i}", "namespace": "default",
                     "uid": f"uid-bench-{i}", "annotations": {}},
        "spec": {"containers": [{
            "name": "main",
            "resources": {"limits": {"google.com/tpumem": str(mem)}},
        }]},
    }


def _post(port: int, path: str, payload: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        return json.loads(resp.read())


def _pct(samples: list[float], q: float) -> float:
    if len(samples) < 2:  # degenerate run: still report what we saw
        return samples[0] if samples else 0.0
    return statistics.quantiles(samples, n=100)[int(q) - 1]


def _stats_ms(samples: list[float]) -> dict:
    return {
        "p50": round(_pct(samples, 50) * 1e3, 2),
        "p99": round(_pct(samples, 99) * 1e3, 2),
        "mean": round(statistics.mean(samples) * 1e3, 2) if samples else 0.0,
    }


def _histogram_stats(port: int) -> dict:
    """The product's own histogram families, scraped over /metrics."""
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
        text = r.read().decode()
    out = {}
    for fam in ("vtpu_scheduler_filter_seconds", "vtpu_scheduler_bind_seconds"):
        count = total = 0.0
        for line in text.splitlines():
            if line.startswith(f"{fam}_count"):
                count = float(line.split()[-1])
            elif line.startswith(f"{fam}_sum"):
                total = float(line.split()[-1])
        out[fam] = {"count": count, "mean_ms": (total / count * 1e3) if count else 0.0}
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=100)
    ap.add_argument("--pods", type=int, default=1000)
    ap.add_argument("--chips-per-node", type=int, default=8)
    ap.add_argument("--patch-rtt-ms", type=float, default=0.0,
                    help="emulated apiserver write RTT (fake client)")
    ap.add_argument("--concurrency", type=int, default=1,
                    help="parallel filter/bind pipelines")
    ap.add_argument("--candidates", type=int, default=0,
                    help="candidate nodes per filter (0 = the whole fleet). "
                    "kube-scheduler samples candidates at large fleet sizes "
                    "(percentageOfNodesToScore), so the extender rarely sees "
                    "every node; this measures that realistic configuration")
    a = ap.parse_args()

    client = FakeKubeClient()
    client.write_rtt_s = a.patch_rtt_ms / 1e3
    for n in range(a.nodes):
        node = f"node-{n:03d}"
        client.put_node({"metadata": {
            "name": node,
            "annotations": {
                REGISTER_ANNO: codec.encode_node_devices(_devices(node, a.chips_per_node))
            },
        }})
    sched = Scheduler(client)
    backend = TpuDevices(TpuConfig(), quota=sched.quota_manager)
    register_backend(backend)
    sched.quota_manager.refresh_managed_resources()
    sched.start(register_interval=3600)
    server = SchedulerServer(sched, WebHook(sched.quota_manager),
                             host="127.0.0.1", port=0)
    server.start_background()

    node_names = [f"node-{n:03d}" for n in range(a.nodes)]
    filter_s: list[float] = []
    bind_s: list[float] = []
    failed = 0

    def candidates_for(i: int) -> list[str]:
        if not a.candidates or a.candidates >= a.nodes:
            return node_names
        # rotating window: spreads load across the fleet like the
        # kube-scheduler's candidate sampling cursor
        start = (i * a.candidates) % a.nodes
        window = node_names[start:start + a.candidates]
        return window + node_names[: a.candidates - len(window)]

    # Register-loop cost at this fleet width: one
    # steady-state pass (byte-identical annotations -> decode skipped) vs
    # one cold pass (cache cleared -> full decode + re-clone).
    t0 = time.perf_counter()
    sched.register_from_node_annotations()
    register_warm_s = time.perf_counter() - t0
    sched._register_seen.clear()
    t0 = time.perf_counter()
    sched.register_from_node_annotations()
    register_cold_s = time.perf_counter() - t0

    if a.concurrency > 1:
        # Concurrent filter pipelines (binds are serialized per node by the
        # node lock BY DESIGN, so concurrency is a filter-path experiment):
        # with the decision patch outside the filter lock, N workers overlap
        # their patch RTTs and throughput is bounded by lock-held compute,
        # not lock-held I/O.
        counter = {"i": 0}
        counter_lock = threading.Lock()
        stats_lock = threading.Lock()
        fails = [0]

        def pipeline() -> None:
            while True:
                with counter_lock:
                    i = counter["i"]
                    if i >= a.pods:
                        return
                    counter["i"] = i + 1
                try:
                    pod = client.put_pod(_pod(i))
                    t0 = time.perf_counter()
                    r = _post(server.port, "/filter",
                              {"Pod": pod, "NodeNames": candidates_for(i)})
                    dt = time.perf_counter() - t0
                except Exception as exc:  # lost sample must be VISIBLE
                    with stats_lock:
                        fails[0] += 1
                    print(f"pipeline error on pod {i}: {exc}", file=sys.stderr)
                    continue
                with stats_lock:
                    filter_s.append(dt)
                    if not r.get("NodeNames"):
                        fails[0] += 1

        t_start = time.perf_counter()
        threads = [threading.Thread(target=pipeline) for _ in range(a.concurrency)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall = time.perf_counter() - t_start
        failed = fails[0]
    else:
        wall, failed = _sequential(a, client, server, candidates_for, filter_s, bind_s)

    result = {
        "nodes": a.nodes,
        "pods": a.pods,
        "chips_per_node": a.chips_per_node,
        "patch_rtt_ms": a.patch_rtt_ms,
        "concurrency": a.concurrency,
        "candidates_per_filter": a.candidates or a.nodes,
        "register_pass_ms": {
            "cold_full_decode": round(register_cold_s * 1e3, 1),
            "steady_state": round(register_warm_s * 1e3, 1),
        },
        "failed": failed,
        "samples": len(filter_s),
        "wall_seconds": round(wall, 2),
        "pods_per_second": round(a.pods / wall, 1),
        "filter_ms": _stats_ms(filter_s),
        "bind_ms": _stats_ms(bind_s),
        "histograms": _histogram_stats(server.port),
    }
    server.shutdown()
    sched.stop()
    json.dump(result, sys.stdout, indent=2)
    print()


def _sequential(a, client, server, candidates_for, filter_s, bind_s) -> tuple[float, int]:
    failed = 0
    t_start = time.perf_counter()
    for i in range(a.pods):
        pod = client.put_pod(_pod(i))
        t0 = time.perf_counter()
        r = _post(server.port, "/filter", {"Pod": pod, "NodeNames": candidates_for(i)})
        filter_s.append(time.perf_counter() - t0)
        if not r.get("NodeNames"):
            failed += 1
            continue
        t0 = time.perf_counter()
        rb = _post(server.port, "/bind", {
            "PodName": pod["metadata"]["name"],
            "PodNamespace": "default",
            "Node": r["NodeNames"][0],
        })
        bind_s.append(time.perf_counter() - t0)
        if rb.get("Error"):
            failed += 1
            continue
        # Emulate the kubelet Allocate step outside the timed window: the
        # device plugin releases the bind's node lock on success (plugin
        # server.py Allocate); without it every later bind times out on
        # lock contention instead of measuring bind cost.
        nodelock.release_node_lock(client, r["NodeNames"][0],
                                   client.get_pod("default", pod["metadata"]["name"]))
    return time.perf_counter() - t_start, failed


if __name__ == "__main__":
    main()

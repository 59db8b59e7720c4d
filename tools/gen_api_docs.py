"""Generate docs/API.md from the public runtime API's docstrings.

The docstring audit (DESIGN.md §9 satellite) made every public symbol of
the futurized runtime, the frontend, and the multi-locality runtime
carry args/returns/raises; this script turns those docstrings into one
browsable reference so the docs can never drift silently - CI and
tests/test_docs.py run ``--check``, which fails when docs/API.md is stale.

    PYTHONPATH=src python tools/gen_api_docs.py            # regenerate
    PYTHONPATH=src python tools/gen_api_docs.py --check    # CI gate
"""
import argparse
import importlib
import inspect
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "src"))

# (module, [symbol or "Class.method" ...]); order is the document order
API = [
    ("repro.core.futures", [
        "Lane", "PhyFuture",
        "PhyFuture.result", "PhyFuture.cancel", "PhyFuture.exception",
        "PhyFuture.add_done_callback", "PhyFuture.set_result",
        "PhyFuture.set_exception",
        "FuturizedGraph",
        "FuturizedGraph.defer", "FuturizedGraph.immediate",
        "FuturizedGraph.promise", "FuturizedGraph.when_all",
        "FuturizedGraph.when_any", "FuturizedGraph.tree_join",
        "FuturizedGraph.gather", "FuturizedGraph.barrier",
        "FuturizedGraph.stats", "FuturizedGraph.shutdown",
        "FuturizedGraph.add_trace_hook",
        "FuturizedGraph.record_serve",
        "RuntimeStats", "Pipeline", "hist_labels",
    ]),
    ("repro.core.paging", [
        "PageError",
        "PagePool", "PagePool.alloc", "PagePool.free",
        "PagePool.write", "PagePool.read", "PagePool.owners",
        "PagePool.counters",
        "InferenceCache", "InferenceCache.put", "InferenceCache.get",
        "InferenceCache.drop", "InferenceCache.counters",
    ]),
    ("repro.frontend", [
        "Plan", "Plan.compile",
        "Session", "Session.train", "Session.serve",
        "Session.serve_stream", "Session.close", "Session.stats",
        "Session.kill_locality", "Session.add_locality", "Session.lint",
        "futurize", "tracing", "Trace", "serve_flags",
    ]),
    ("repro.frontend.gateway", [
        "RequestQueue", "RequestQueue.submit", "RequestQueue.close",
        "RequestHandle", "RequestHandle.result", "RequestHandle.cancel",
        "Gateway", "Gateway.run",
        "RequestRejected", "DeadlineExpired",
    ]),
    ("repro.analysis.lint", [
        "Finding", "LintGraph",
        "LintGraph.add", "LintGraph.mark_forced",
        "LintGraph.from_trace", "LintGraph.from_graph",
        "lint",
    ]),
    ("repro.analysis.sanitize", [
        "Diagnostic", "DeadlockError",
        "Sanitizer", "Sanitizer.record", "Sanitizer.diagnostics",
        "Sanitizer.clear",
        "get", "active", "enabled", "config",
        "find_cycle", "thread_stacks",
    ]),
    ("repro.analysis.trace_builders", [
        "train_trace", "serve_trace", "gateway_trace", "step_contract",
        "plan_traces",
    ]),
    ("repro.distrib", [
        "Endpoint", "Endpoint.register", "Endpoint.connect",
        "Endpoint.request", "Endpoint.post", "Endpoint.close",
        "raw_request",
        "ObjectDirectory", "ObjectDirectory.put", "ObjectDirectory.fetch",
        "ObjectDirectory.free", "ObjectDirectory.rebalance",
        "ObjectDirectory.audit", "RemoteRef", "rebalance_plan",
        "DistributedGraph", "DistributedGraph.defer",
        "DistributedGraph.add_locality", "DistributedGraph.rebalance",
        "DistributedGraph.replicate", "DistributedGraph.cancel",
        "DistributedGraph.fetch", "DistributedGraph.stats",
        "DistributedGraph.remote_stats", "DistributedGraph.barrier",
        "DistributedGraph.shutdown", "DistributedGraph.spmd_train",
        "DistributedGraph.spmd_entry_futures",
        "DistributedGraph.wait_spmd_done",
        "DistributedGraph.account_ckpt_leaf_bytes",
        "DistributedGraph.ddp_train", "DistributedGraph.wait_ddp_done",
        "DistributedGraph.ddp_abort",
        "DistributedGraph.account_grad_wire_bytes",
        "Locality", "LocalityGroup", "LocalityGroup.kill",
        "LocalityGroup.add_worker", "worker_main", "join_locality",
    ]),
    ("repro.distrib.collectives", [
        "GradCodec", "GradCodec.reset", "GradCodec.encode",
        "GradCodec.decode", "GradCodec.wire_bytes",
        "Fp32Codec", "OneBitCodec", "get_codec",
        "RingAllReduce", "RingAllReduce.configure",
        "RingAllReduce.allreduce", "RingAllReduce.abort",
        "RingAllReduce.peer_lost", "RingAllReduce.deactivate",
    ]),
    ("repro.frontend.spmd", [
        "shadow_train",
    ]),
    ("repro.frontend.ddp", [
        "DDPEngine", "DDPEngine.init", "DDPEngine.train_step",
        "ddp_shadow_train", "shard_batch",
    ]),
    ("repro.data.pipeline", [
        "Prefetcher", "Prefetcher.schedule", "Prefetcher.get_future",
        "stream_for",
    ]),
    ("repro.checkpoint.checkpoint", [
        "CheckpointManager", "CheckpointManager.save",
        "CheckpointManager.restore", "CheckpointManager.wait",
        "CheckpointManager.ranks", "CheckpointManager.close",
    ]),
    ("repro.checkpoint.format", [
        "CheckpointCorruptError", "assign_shards", "save_shard",
        "read_shard", "read_shard_segments", "assemble_leaf",
        "build_manifest", "commit_manifest",
        "load_manifest", "shard_filename", "writer_rank",
    ]),
    ("repro.checkpoint.spmd", [
        "is_multiprocess", "persistence_mesh", "persistence_sharding",
        "global_view", "addressable_segments", "collect_segments",
        "write_spmd_shard", "device_put_maybe_global",
    ]),
]

HEADER = """\
# phyrax public API

*Generated by `tools/gen_api_docs.py` from source docstrings - do not
edit by hand; regenerate with*
`PYTHONPATH=src python tools/gen_api_docs.py`.
*CI (`.github/workflows/ci.yml`, multiproc job) and `tests/test_docs.py`
fail when this file is stale.*
"""


def _signature(obj) -> str:
    try:
        return str(inspect.signature(obj))
    except (TypeError, ValueError):
        return ""


def _doc(obj) -> str:
    doc = inspect.getdoc(obj)
    return doc.strip() if doc else "*(undocumented)*"


def render() -> str:
    out = [HEADER]
    for mod_name, symbols in API:
        mod = importlib.import_module(mod_name)
        out.append(f"\n## `{mod_name}`\n")
        mod_doc = inspect.getdoc(mod)
        if mod_doc:
            out.append(mod_doc.split("\n\n")[0] + "\n")
        for sym in symbols:
            parts = sym.split(".")
            obj = mod
            for p in parts:
                obj = getattr(obj, p)
            title = f"`{sym}`"
            if inspect.isclass(obj):
                out.append(f"### {title}\n")
                sig = _signature(obj)
                if sig:
                    out.append(f"```python\n{sym}{sig}\n```\n")
            else:
                depth = "####" if len(parts) > 1 else "###"
                out.append(f"{depth} {title}\n")
                sig = _signature(obj)
                if sig:
                    out.append(f"```python\n{parts[-1]}{sig}\n```\n")
            out.append(textwrap.dedent(_doc(obj)) + "\n")
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="exit 1 if docs/API.md is stale instead of writing")
    args = ap.parse_args()
    target = ROOT / "docs" / "API.md"
    text = render()
    if args.check:
        current = target.read_text() if target.exists() else ""
        if current != text:
            print(f"STALE: {target} does not match the docstrings; "
                  f"regenerate with PYTHONPATH=src python "
                  f"tools/gen_api_docs.py")
            raise SystemExit(1)
        print(f"OK: {target} is current "
              f"({sum(len(s) for _, s in API)} symbols)")
        return
    target.parent.mkdir(parents=True, exist_ok=True)
    target.write_text(text)
    print(f"wrote {target} ({len(text.splitlines())} lines, "
          f"{sum(len(s) for _, s in API)} symbols)")


if __name__ == "__main__":
    main()

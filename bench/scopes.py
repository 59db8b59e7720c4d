"""Device time by named scope (``jax.named_scope``: ``mla``, ``moe.route``,
``moe.dispatch``, ``moe.experts``, ``moe.combine``, ``moe.shared``).

The profiler trace's op events carry the HLO instruction (``%fusion.12 =
...``) but not the name stack it came from.  So ``op_scopes`` reads each
instruction's ``metadata={op_name="..."}`` in the compiled program's
optimized HLO text, and ``scope_seconds`` matches the trace's ops to it by
instruction name.  An op counts under the innermost scope of its name
stack (forward, recomputed and transposed ops alike: ``transpose(jvp(
moe.experts))`` is ``moe.experts``).  A fusion carries its root's
metadata, so a fusion that straddles two scopes goes wholly to its root's.
"""
from __future__ import annotations

import collections
import re

import trace_modules
import trace_reduce

SCOPE = re.compile(r"(?:^|[/(])(mla|moe\.[a-z]+)(?=[/)]|$)")
_INSTR = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?"
                    r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")


def op_scopes(hlo_text: str) -> dict:
    """``{instruction name: innermost scope}`` of every instruction of the
    optimized HLO text whose op_name names a scope."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            found = SCOPE.findall(m.group(2))
            if found:
                out[m.group(1)] = found[-1]
    return out


def scope_seconds(pd, op_scope: dict) -> dict:
    """Device seconds in the ``bench.window`` span of a loaded
    ``jax.profiler.ProfileData`` by scope (the union of its ops'
    intervals, so nested ops count once), and ``moe`` for every ``moe.*``
    scope together; averaged over the device planes."""
    lo, hi = trace_modules.window(pd)
    planes = trace_modules._device_planes(pd)
    total: collections.Counter = collections.Counter()
    for plane in planes:
        ivs = collections.defaultdict(list)
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for e in line.events:
                scope = op_scope.get(trace_reduce.op_name(e.name))
                a, b = max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi)
                if scope is None or b <= a:
                    continue
                ivs[scope].append([a, b])
                if scope.startswith("moe."):
                    ivs["moe"].append([a, b])
        for scope, iv in ivs.items():
            total[scope] += trace_reduce.length(trace_reduce.union(iv)) / 1e9
    n = max(len(planes), 1)
    return {k: v / n for k, v in total.items()}

"""How often each compiled op under a ``jax.named_scope`` ran in a trace.

``lib/xplane_scopes.by_pattern`` sums device time by scope; a roofline also
needs the WORK the traced span held, and the span is short against a tick of
the engine, so the program's counters (which move a tick at a time) cannot
give it.  The trace can: an op of an unrolled program runs once each time
its program (or its loop body) runs, so how often the ops under a scope ran
is how often that part of the program ran, a partly traced run counted by the
part of its ops that fell inside.
"""

import re

from lib import xplane, xplane_scopes


def executions(path: str, patterns: dict) -> dict:
    """``{key: {label: {op: runs}}}`` for the first chip that ran ops:
    ``patterns`` is ``{key: regex}`` searched in an op's ``tf_op`` scope;
    ``label`` is the tuple of the regex's groups (``()`` where it has none),
    ``op`` the compiled op's metadata id.  None without a device plane."""
    space = xplane_scopes.read_space(path)
    compiled = {k: re.compile(p) for k, p in patterns.items()}
    for plane in sorted(space.planes, key=lambda p: p.name):
        if not xplane.DEVICE_PLANE.match(plane.name):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        metas = {e.key: e.value for e in plane.event_metadata}
        hits = {}  # metadata id -> [(key, label)]
        out = {k: {} for k in patterns}
        seen = False
        for line in plane.lines:
            if line.name != xplane.OPS_LINE:
                continue
            for ev in line.events:
                seen = True
                if ev.metadata_id not in hits:
                    meta = metas.get(ev.metadata_id)
                    name = xplane.op_name(meta.name) if meta else ""
                    scope = (
                        xplane_scopes._scope_of(meta, stat_names) if meta else ""
                    )
                    found = []
                    if not xplane.CONTAINER.match(name):
                        for key, rx in compiled.items():
                            m = rx.search(scope)
                            if m:
                                found.append((key, m.groups()))
                    hits[ev.metadata_id] = found
                for key, label in hits[ev.metadata_id]:
                    ops = out[key].setdefault(label, {})
                    ops[ev.metadata_id] = ops.get(ev.metadata_id, 0) + 1
        if seen:
            return out
    return None

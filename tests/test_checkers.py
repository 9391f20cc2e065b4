"""The repo's AST contract gates, behind ONE tier-1 entry point.

``scripts/check_all.py`` registers every static checker (injectable
clock, named-scope collectives, host-sync-free serving loops, fenced
block-table mutation); ``test_all_ast_gates`` runs the whole registry
over the live tree exactly as CI would — adding the next checker is one
registry line plus its module, and it is gated here automatically.  The
per-checker self-tests (the proof each gate still CATCHES violations and
honors its exemptions) live alongside, consolidated from the four files
that used to wire them individually.
"""

import os
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
try:
    import check_all
finally:
    sys.path.pop(0)


def _load(name):
    return check_all.load_checker(name)


# -- the single tier-1 entry point -----------------------------------------


def test_all_ast_gates():
    """Every registered gate passes over the live tree.  One assertion
    per gate so a violation names its contract, not just a count."""
    results = check_all.run_all()
    assert set(results) == set(check_all.CHECKERS)
    for name, problems in results.items():
        assert problems == [], (
            f"{name} ({check_all.CHECKERS[name]}):\n" + "\n".join(problems)
        )


def test_check_all_rejects_unknown_checker():
    with pytest.raises(ValueError):
        check_all.run_all(["no_such_gate"])


# -- per-checker self-tests (the catch paths) ------------------------------


def test_check_clock_semantics():
    """The clock gate catches attribute calls, from-imports and sleep —
    while a clock DEFAULT (dependency injection) stays legal."""
    cc = _load("check_clock")
    bad = (
        "import time\n"
        "from time import monotonic as mono\n"
        "def f():\n"
        "    a = time.time()\n"
        "    b = mono()\n"
        "    time.sleep(1)\n"
        "def ok(clock=time.monotonic):\n"
        "    return clock()\n"
    )
    found = cc.check_source(bad, "x.py")
    assert len(found) == 3
    assert any("time.time()" in p for p in found)
    assert any("mono()" in p for p in found)
    assert any("time.sleep()" in p for p in found)


def test_check_clock_walk_covers_autopilot():
    """New-module pickup: the clock gate's default paths are DIRECTORY
    walks, so the SLO autopilot (cluster/autopilot.py) is covered with
    zero registry changes — the walk finds it, and a wall-time call in
    it would be flagged."""
    cc = _load("check_clock")
    cluster_dir = os.path.join(REPO_ROOT, "tpu_parallel", "cluster")
    walked = [
        os.path.join(root, f)
        for root, _, names in os.walk(cluster_dir)
        for f in names
        if f.endswith(".py")
    ]
    assert any(f.endswith("autopilot.py") for f in walked)
    assert "tpu_parallel/cluster" in cc.DEFAULT_PATHS
    planted = "import time\ndef f():\n    return time.monotonic()\n"
    flagged = cc.check_source(
        planted, "tpu_parallel/cluster/autopilot.py"
    )
    assert len(flagged) == 1 and "monotonic" in flagged[0]


def test_check_scopes_semantics():
    """The collective gate flags an unscoped psum and honors with-block
    scopes, decorator scopes (nested-def scan bodies) and the axis-size
    probe exemption."""
    cs = _load("check_scopes")
    flagged = cs.check_source(
        "def f(x):\n    return lax.psum(x, 'data')\n", "f.py"
    )
    assert len(flagged) == 1 and "psum" in flagged[0]
    for ok_src in (
        "def f(x):\n"
        "    with jax.named_scope('s'):\n"
        "        return lax.psum(x, 'data')\n",
        "@jax.named_scope('s')\n"
        "def f(x):\n"
        "    def body(c, _):\n"
        "        return lax.ppermute(c, 'pipe', perm=[(0, 1)]), None\n"
        "    return body(x, None)\n",
        "def f():\n    return lax.psum(1, 'data')\n",
    ):
        assert cs.check_source(ok_src, "ok.py") == [], ok_src


def test_check_host_sync_semantics():
    """The host-sync gate flags loop-body and per-iteration comprehension
    syncs, honors the ``# host-sync:`` whitelist (anywhere in a wrapped
    call's span), leaves loop-free syncs legal, and fails loudly on a
    typo'd path."""
    chs = _load("check_host_sync")
    bad = (
        "import numpy as np\n"
        "def f(slots, fetch):\n"
        "    for s in slots:\n"
        "        a = np.asarray(fetch(s))\n"
        "        fetch(s).block_until_ready()\n"
        "    while slots:\n"
        "        b = np.asarray(slots.pop())  # host-sync: tick-boundary\n"
        "    c = np.asarray(fetch(0))\n"
        "def g(xs, fetch):\n"
        "    return [np.asarray(fetch(x)) for x in xs]\n"
        "def h(dev_batch):\n"
        "    return [int(t) for t in np.asarray(dev_batch)]\n"
    )
    found = chs.check_source(bad, "x.py")
    assert len(found) == 3, found
    assert any("np.asarray" in p and ":4:" in p for p in found)
    assert any("block_until_ready" in p for p in found)
    assert any(":10:" in p for p in found)
    wrapped = (
        "import numpy as np\n"
        "def f(slots, fetch):\n"
        "    while slots:\n"
        "        b = np.asarray(\n"
        "            fetch(slots.pop())\n"
        "        )  # host-sync: tick-boundary\n"
    )
    assert chs.check_source(wrapped, "x.py") == []
    with pytest.raises(FileNotFoundError):
        chs.check_paths((os.path.join(REPO_ROOT, "no_such_dir"),))


def test_check_host_sync_launch_rule():
    """The launch/collect overlap gate: ANY device sync lexically inside
    a ``launch``/``_launch*`` body flags (loop or no loop — one sync on
    the launch side serializes the double-buffered pipeline), including
    inside nested defs, while collect-side syncs, launch-side host-only
    numpy, and the ``# host-sync:`` whitelist stay legal."""
    chs = _load("check_host_sync")
    bad = (
        "import numpy as np\n"
        "class Engine:\n"
        "    def launch(self, fetch):\n"
        "        a = np.asarray(fetch())\n"
        "        fetch().block_until_ready()\n"
        "    def _launch_fused(self, fetch):\n"
        "        def inner():\n"
        "            return np.asarray(fetch())\n"
        "        return inner()\n"
        "    def collect(self, pending):\n"
        "        return np.asarray(pending)\n"
    )
    found = chs.check_source(bad, "engine.py")
    assert len(found) == 3, found
    assert all("launch body" in p for p in found)
    assert any(":4:" in p for p in found)
    assert any(":5:" in p for p in found)
    assert any(":8:" in p for p in found)
    ok = (
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "class Engine:\n"
        "    def launch(self, prompt):\n"
        "        block = np.zeros((4, 8), np.int32)\n"
        "        dev = jnp.asarray(block)\n"
        "        legal = np.asarray(prompt)  # host-sync: host list\n"
        "        return dev, legal\n"
        "    def relaunch_probe(self, fetch):\n"
        "        return np.asarray(fetch())\n"
    )
    assert chs.check_source(ok, "engine.py") == []
    # the rule holds for what launch REACHES in its file (step() runs the
    # next launch before the pending collect: a sync in an admission
    # helper idles the device just the same), through self.<name>() and
    # <name>() calls; what only the collect side calls stays legal
    reached = (
        "import numpy as np\n"
        "class Engine:\n"
        "    def launch(self):\n"
        "        return self._admit()\n"
        "    def _admit(self):\n"
        "        return first_tokens(self._sample())\n"
        "    def _sample(self):\n"
        "        return np.asarray(self.logits)\n"
        "    def collect(self, pending):\n"
        "        return self._deliver(pending)\n"
        "    def _deliver(self, pending):\n"
        "        return np.asarray(pending)\n"
        "def first_tokens(x):\n"
        "    return x.block_until_ready()\n"
    )
    found = chs.check_source(reached, "engine.py")
    assert len(found) == 2, found
    assert any(":8:" in p for p in found) and any(":14:" in p for p in found)
    # the live engine's launch side is clean — the gate would catch a
    # regression that moved a sync back before the dispatch
    results = chs.check_paths()
    assert results == [], results


def test_check_blocks_semantics():
    """The block-table gate catches subscript stores, augmented stores
    and deletes; reads, copies and local rebinds stay legal, and the
    allocator's own module is the one legal mutation site."""
    cb = _load("check_blocks")
    bad = (
        "def f(pool, t):\n"
        "    pool.block_table[0, 1] = 3\n"
        "    pool.block_table[0] += 1\n"
        "    self._block_table[s][j] = 9\n"
        "    del pool.block_table[0]\n"
    )
    found = cb.check_source(bad, "x.py")
    assert len(found) == 4, found
    ok = (
        "def g(pool, np, jnp):\n"
        "    row = pool.block_table[0]\n"
        "    table = np.asarray(pool.block_table)\n"
        "    block_table = jnp.zeros(4)\n"
        "    other[0] = pool.block_table[1]\n"
        "    return row, table, block_table\n"
    )
    assert cb.check_source(ok, "x.py") == []
    assert cb.check_source(bad, "cache_pool.py") == []
    with pytest.raises(FileNotFoundError):
        cb.check_paths((os.path.join(REPO_ROOT, "no_such_dir"),))


def test_check_blocks_allocator_reference_fence():
    """The widened gate (KV hierarchy PR): direct allocator reference
    mutation — ``*.allocator.alloc/free/share(...)`` — is fenced outside
    ``cache_pool.py`` exactly like raw table stores, because the radix
    tree, the host offload tier and the migration shim HOLD references
    but must take and drop them through the pool's surface.  Reads
    (``check`` / ``refcount`` / properties) stay legal everywhere, and
    the pool's own module keeps its authority."""
    cb = _load("check_blocks")
    bad = (
        "def f(pool, radix):\n"
        "    b = pool.allocator.alloc()\n"
        "    pool.allocator.share(b)\n"
        "    radix.pool.allocator.free(b)\n"
    )
    found = cb.check_source(bad, "kv_hierarchy.py")
    assert len(found) == 3, found
    assert all("block reference" in p for p in found)
    ok = (
        "def g(pool):\n"
        "    pool.allocator.check()\n"
        "    r = pool.allocator.refcount(0)\n"
        "    n = pool.allocator.n_free\n"
        "    blocks = pool.snapshot_blocks(0, 8)\n"
        "    pool.pin_blocks(blocks)\n"
        "    pool.free_stored(blocks)\n"
        "    return r, n\n"
        "def h(alloc):\n"
        "    return alloc()  # a bare callable is not the allocator\n"
    )
    assert cb.check_source(ok, "x.py") == []
    assert cb.check_source(bad, "cache_pool.py") == []
    # the walk covers the new module: a planted violation IN
    # kv_hierarchy.py would be flagged by the default paths
    assert any(
        "tpu_parallel/serving" in p for p in cb.DEFAULT_PATHS
    )


def test_check_clock_daemon_walk_and_wallclock_exemption():
    """The clock gate's daemon extension (PR 14): the walk now covers
    ``tpu_parallel/daemon/`` — a wall-time call anywhere in the daemon
    package is flagged — EXCEPT ``daemon/wallclock.py``, the one
    sanctioned adapter, whose real source would otherwise trip the gate
    (proving the exemption is load-bearing, not decorative)."""
    cc = _load("check_clock")
    assert "tpu_parallel/daemon" in cc.DEFAULT_PATHS
    # the live daemon tree passes (wallclock.py skipped by exemption)
    daemon_dir = os.path.join(REPO_ROOT, "tpu_parallel", "daemon")
    assert cc.check_paths((daemon_dir,)) == []
    # the exemption matches exactly the adapter, nothing else
    assert cc.is_wallclock_file("tpu_parallel/daemon/wallclock.py")
    assert cc.is_wallclock_file(
        os.path.join(daemon_dir, "wallclock.py")
    )
    assert not cc.is_wallclock_file("tpu_parallel/daemon/daemon.py")
    assert not cc.is_wallclock_file("tpu_parallel/serving/engine.py")
    # wallclock.py's REAL source is only legal BECAUSE of the exemption
    with open(os.path.join(daemon_dir, "wallclock.py")) as fh:
        src = fh.read()
    assert cc.check_source(src, "wallclock.py"), (
        "wallclock.py no longer reads wall time — the exemption (and "
        "this test) should be retired"
    )
    # a wall-time read planted elsewhere in the daemon package IS
    # caught by the same walk
    bad = "import time\ndef pump():\n    return time.monotonic()\n"
    assert cc.check_source(bad, "tpu_parallel/daemon/daemon.py")


def test_checkers_cover_fleet_tree():
    """The fleet extension (PR 16): all three behavioral gates walk
    ``tpu_parallel/fleet/`` — the live tree passes, and a planted
    violation in a fleet-path filename is flagged by each gate, proving
    the registration is load-bearing."""
    fleet_dir = os.path.join(REPO_ROOT, "tpu_parallel", "fleet")
    assert os.path.isdir(fleet_dir)

    cc = _load("check_clock")
    assert "tpu_parallel/fleet" in cc.DEFAULT_PATHS
    assert cc.check_paths((fleet_dir,)) == []
    planted = "import time\ndef probe():\n    return time.monotonic()\n"
    assert cc.check_source(planted, "tpu_parallel/fleet/router.py")

    ci = _load("check_io")
    assert "tpu_parallel/fleet" in ci.DEFAULT_PATHS
    assert ci.check_paths((fleet_dir,)) == []
    planted = "def dump(path, blob):\n    open(path, 'wb').write(blob)\n"
    assert ci.check_source(planted, "tpu_parallel/fleet/router.py")

    chs = _load("check_host_sync")
    assert "tpu_parallel/fleet" in chs.DEFAULT_PATHS
    assert chs.check_paths((fleet_dir,)) == []
    planted = (
        "import numpy as np\n"
        "def relay(evs, fetch):\n"
        "    for ev in evs:\n"
        "        yield np.asarray(fetch(ev))\n"
    )
    assert chs.check_source(planted, "tpu_parallel/fleet/router.py")


def test_check_fleet_registered_as_runtime_gate():
    """``check_fleet`` (the multi-process fleet smoke) rides the
    RUNTIME_CHECKS registry like ``check_daemon``: resolvable by name,
    excluded from the instant AST sweep; the smoke itself runs as its
    own tier-1 entry in tests/test_fleet.py."""
    assert "check_fleet" in check_all.RUNTIME_CHECKS
    assert "check_fleet" not in check_all.CHECKERS
    mod = check_all.load_checker("check_fleet")
    assert callable(mod.check_paths)


def test_check_fleet_static_verdict_accounting():
    """``check_fleet``'s static layer: the live tree passes (role
    vocabulary present, every ``kv_import`` caller accounts its import
    verdicts), a planted bypass — KV shipped with the verdicts dropped
    on the floor — is flagged, and a gutted role vocabulary is too."""
    cf = _load("check_fleet")
    assert cf.check_static() == []
    planted = (
        "def sneak_handoff(self, dst, blob):\n"
        "    code, body = self.transport.kv_import(dst, blob, 5.0)\n"
        "    return code == 200\n"
    )
    found = cf.check_source(planted, "tpu_parallel/fleet/router.py")
    assert len(found) == 1 and "sneak_handoff" in found[0]
    assert "verdict" in found[0]
    # the same shipping path WITH accounting passes
    ok = (
        "def handoff(self, dst, blob):\n"
        "    code, body = self.transport.kv_import(dst, blob, 5.0)\n"
        "    for v, n in body.get('verdicts', {}).items():\n"
        "        self.registry.counter(\n"
        "            'fleet_kv_imports_total', status=v).inc(n)\n"
        "    return code == 200\n"
    )
    assert cf.check_source(ok, "tpu_parallel/fleet/router.py") == []
    # the roles module must keep its full vocabulary
    gutted = "ROLES = ('prefill', 'decode')\n"
    found = cf.check_source(gutted, "tpu_parallel/fleet/roles.py")
    assert len(found) == 1 and "mixed" in found[0]
    found = cf.check_source("X = 1\n", "tpu_parallel/fleet/roles.py")
    assert len(found) == 1 and "no ROLES" in found[0]
    with pytest.raises(FileNotFoundError):
        cf.check_static(("no/such/module.py",))


def test_runtime_checks_registered_separately():
    """``check_daemon`` (the start/submit/SIGTERM-drain smoke) lives in
    the RUNTIME_CHECKS registry: resolvable by name like the AST gates,
    but excluded from the default ``run_all()`` sweep so
    ``test_all_ast_gates`` stays instant — the smoke itself runs as its
    own tier-1 entry in tests/test_daemon.py."""
    assert "check_daemon" in check_all.RUNTIME_CHECKS
    assert "check_daemon" not in check_all.CHECKERS
    mod = check_all.load_checker("check_daemon")
    assert callable(mod.check_paths)
    with pytest.raises(ValueError):
        check_all.load_checker("no_such_gate")


def test_check_io_semantics():
    """The IO-shim gate flags raw open / os.fsync / os.write, honors
    the ``# raw-io:`` escape hatch, exempts the shim module itself, and
    fails loudly on a typo'd path — the coverage guarantee behind the
    seeded disk-fault soak."""
    ci = _load("check_io")
    bad = (
        "import os\n"
        "def f(path, fd, data):\n"
        "    fh = open(path)\n"
        "    os.fsync(fd)\n"
        "    os.write(fd, data)\n"
        "    legal = open(path)  # raw-io: reads a config, not a journal\n"
        "def g(path):\n"
        "    with open(\n"
        "        path, 'rb'\n"
        "    ) as fh:  # raw-io: wrapped-call annotation spans lines\n"
        "        return fh.read()\n"
    )
    found = ci.check_source(bad, "tpu_parallel/daemon/x.py")
    assert len(found) == 3, found
    assert any("open()" in p and ":3:" in p for p in found)
    assert any("os.fsync()" in p for p in found)
    assert any("os.write()" in p for p in found)
    # the shim module is the one legal raw-IO site
    assert ci.check_source(bad, "tpu_parallel/daemon/iofaults.py") == []
    # methods/attributes named open on other objects stay legal
    ok = (
        "def h(fh, gz, os_mod):\n"
        "    a = fh.open()\n"
        "    b = gz.open('x')\n"
        "    return a, b\n"
    )
    assert ci.check_source(ok, "tpu_parallel/daemon/y.py") == []
    with pytest.raises(FileNotFoundError):
        ci.check_paths((os.path.join(REPO_ROOT, "no_such_dir"),))
    # registered: the registry sweep covers it with zero extra wiring
    import check_all as ca

    assert "check_io" in ca.CHECKERS


def test_check_trace_semantics():
    """The trace gate (tracing PR): a FleetTransport call site under
    fleet/ that omits ``trace=`` is flagged — whether spelled
    ``self.transport.<m>`` or bare ``transport.<m>`` — while an
    explicit ``trace=ctx`` / ``trace=None``, a ``# no-trace: <why>``
    annotation (including on a wrapped call's span), and same-named
    methods on non-transport receivers all stay legal."""
    ct = _load("check_trace")
    bad = (
        "def probe(self, addr):\n"
        "    code, body = self.transport.healthz(addr, 5.0)\n"
        "def route(transport, addr, body):\n"
        "    return transport.submit(addr, body, 5.0)\n"
    )
    found = ct.check_source(bad, "tpu_parallel/fleet/router.py")
    assert len(found) == 2, found
    assert any("healthz" in p and ":2:" in p for p in found)
    assert any("submit" in p and ":4:" in p for p in found)
    ok = (
        "def probe(self, addr, ctx):\n"
        "    a = self.transport.healthz(addr, 5.0, trace=None)\n"
        "    b = self.transport.submit(addr, {}, 5.0, trace=ctx.fork())\n"
        "    c = self.transport.result(addr, 'r', 5.0)  # no-trace: replay\n"
        "    d = self.transport.cancel(\n"
        "        addr, 'r', 5.0,\n"
        "    )  # no-trace: wrapped-call annotation spans lines\n"
        "    e = self.daemon.submit({})\n"
        "    f = client.submit(addr, {})\n"
        "    return a, b, c, d, e, f\n"
    )
    assert ct.check_source(ok, "tpu_parallel/fleet/router.py") == []
    with pytest.raises(FileNotFoundError):
        ct.check_paths((os.path.join(REPO_ROOT, "no_such_dir"),))
    # registered: the registry sweep covers it with zero extra wiring
    assert "check_trace" in check_all.CHECKERS


def test_check_trace_matches_transport_contract():
    """The gate's method set IS the FleetTransport contract: a method
    added to the ABC without updating the gate (or vice versa) fails
    here, so the two cannot drift apart silently."""
    ct = _load("check_trace")
    from tpu_parallel.fleet.router import FleetTransport

    contract = {
        name
        for name in vars(FleetTransport)
        if not name.startswith("_") and callable(
            getattr(FleetTransport, name)
        )
    }
    assert ct.TRANSPORT_METHODS == contract


def test_check_io_fences_kv_disk_tier():
    """The SSD KV tier is in the IO gate's default sweep: the live
    module passes (every byte routes through iofaults), and a planted
    raw ``open`` in it would be a CI failure."""
    ci = _load("check_io")
    kv_disk = "tpu_parallel/serving/kv_disk.py"
    assert kv_disk in ci.DEFAULT_PATHS
    assert ci.check_paths((os.path.join(REPO_ROOT, kv_disk),)) == []
    planted = (
        "def dump_blob(path, data):\n"
        "    with open(path, 'wb') as fh:\n"
        "        fh.write(data)\n"
    )
    found = ci.check_source(planted, kv_disk)
    assert len(found) == 1 and "open()" in found[0]

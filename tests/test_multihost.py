"""Multi-host correctness: a real 2-process jax.distributed CPU cluster.

The reference never leaves one process (``util.py:31-38``); the framework's
multi-host paths (``runtime.initialize``, per-process loader shards,
``make_global_batch``, cross-process collectives in the DP step) were until
now only exercised on a single-process simulated mesh, where every
multi-host bug is invisible.  These tests spawn two local worker processes
(4 simulated CPU devices each -> one 8-device cluster over gloo) via
``tests/multihost_worker.py`` and compare against single-process ground
truth computed in this process.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

# the 2-process cluster needs a free port; without portpicker the whole
# module SKIPS cleanly instead of erroring at collection
pytest.importorskip("portpicker")

from tpu_parallel.data import DataLoader, TokenDataset, classification_batch

pytestmark = pytest.mark.multihost


@pytest.fixture(scope="module")
def cluster_outputs(tmp_path_factory):
    """Run the 2-process cluster once; yield (outdir, worker0, worker1)."""
    import portpicker

    outdir = tmp_path_factory.mktemp("multihost")
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 256, size=4000, dtype=np.uint16)
    TokenDataset.write_bin(str(outdir / "corpus.bin"), tokens)

    port = portpicker.pick_unused_port()
    worker = os.path.join(os.path.dirname(__file__), "multihost_worker.py")
    env = dict(os.environ)
    env.update(
        # explicit CPU, 4 local devices per process
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(i), "2", str(port), str(outdir)],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for i in range(2)
    ]
    logs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=600)
            logs.append(out)
    finally:
        # a hung worker (e.g. peer died mid-collective) must not leak past
        # the fixture — kill both before re-raising
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    assert all(p.returncode == 0 for p in procs), "\n".join(logs)
    return (
        outdir,
        np.load(outdir / "worker0.npz"),
        np.load(outdir / "worker1.npz"),
    )


def _window_ids(ds, rows):
    """Recover window indices from actual token rows (content-matched, so
    assertions on them are not circular with loader internals)."""
    stream = np.asarray(ds.shards[0][: ds.num_windows * ds.seq_len]).astype(
        np.int32
    )
    ids = []
    for row in rows:
        starts = np.flatnonzero(stream[:: ds.seq_len] == row[0])
        ids.append(
            next(
                int(s)
                for s in starts
                if np.array_equal(
                    stream[s * ds.seq_len : (s + 1) * ds.seq_len], row
                )
            )
        )
    return np.asarray(ids)


def test_loader_shards_disjoint_and_deterministic(cluster_outputs, mesh_data8):
    """Process p holds rows p::P of every batch (recovered from the token
    content each worker actually received) — disjoint, and identical to the
    single-process loader's assignment."""
    outdir, w0, w1 = cluster_outputs
    ds = TokenDataset(str(outdir / "corpus.bin"), seq_len=16)
    ref = DataLoader(ds, mesh_data8, global_batch_size=8, seed=7)
    for step in range(3):
        rows0 = _window_ids(ds, w0["local_tokens"][step])
        rows1 = _window_ids(ds, w1["local_tokens"][step])
        assert set(rows0).isdisjoint(rows1)
        epoch, b = divmod(step, ref.batches_per_epoch)
        order = ref._epoch_order(epoch) + ref._window_offset
        expect = order[b * 8 : (b + 1) * 8]
        np.testing.assert_array_equal(np.sort(np.r_[rows0, rows1]), np.sort(expect))
        np.testing.assert_array_equal(rows0, expect[0::2])
        np.testing.assert_array_equal(rows1, expect[1::2])


def test_global_batch_matches_single_process(cluster_outputs, mesh_data8):
    """The stitched 2-process global batch holds exactly the windows the
    single-process loader yields, in the documented process-interleaved
    device order — token content bit-for-bit."""
    outdir, w0, w1 = cluster_outputs
    ds = TokenDataset(str(outdir / "corpus.bin"), seq_len=16)
    ref = DataLoader(ds, mesh_data8, global_batch_size=8, seed=7)
    for step in range(3):
        # both hosts must see the identical global value
        np.testing.assert_array_equal(
            w0["global_tokens"][step], w1["global_tokens"][step]
        )
        epoch, b = divmod(step, ref.batches_per_epoch)
        order = ref._epoch_order(epoch) + ref._window_offset
        rows = order[b * 8 : (b + 1) * 8]
        # device order: process 0's rows occupy devices 0-3, process 1's 4-7
        expect = ds.batch(np.r_[rows[0::2], rows[1::2]]).tokens
        np.testing.assert_array_equal(w0["global_tokens"][step], expect)


def test_dp_step_matches_single_process(cluster_outputs, mesh_data8):
    """One DP step on the cluster == the same step single-process (params
    agree across hosts bitwise, and with local ground truth numerically)."""
    import jax.numpy as jnp

    from tpu_parallel.core import TrainState
    from tpu_parallel.core.losses import make_classification_loss
    from tpu_parallel.models import MLPClassifier, MLPConfig
    from tpu_parallel.parallel import dp

    outdir, w0, w1 = cluster_outputs
    param_keys = [k for k in w0.files if k not in ("local_tokens", "global_tokens", "loss_sum")]
    assert param_keys
    for k in param_keys:  # replicated state must agree across hosts exactly
        np.testing.assert_array_equal(w0[k], w1[k], err_msg=k)
    np.testing.assert_array_equal(w0["loss_sum"], w1["loss_sum"])

    # ground truth: same model, same rows, same per-process row interleave
    cls_batch = classification_batch(jax.random.PRNGKey(0), 16, 32, 10)
    permuted = jax.tree_util.tree_map(
        lambda x: np.r_[np.asarray(x)[0::2], np.asarray(x)[1::2]], cls_batch
    )
    model = MLPClassifier(MLPConfig(hidden_size=32, dtype=jnp.float32))
    tx = optax.sgd(0.1)

    def init(rng, inputs):
        p = model.init({"params": rng}, jnp.zeros_like(inputs), train=False)[
            "params"
        ]
        return TrainState.create(apply_fn=model.apply, params=p, tx=tx, rng=rng)

    state = dp.make_init(init, mesh=mesh_data8)(
        jax.random.PRNGKey(1), permuted.inputs
    )
    step_fn = dp.make_train_step(
        make_classification_loss("data"),
        num_minibatches=2,
        mesh=mesh_data8,
        donate=False,
    )
    state, metrics = step_fn(state, None, permuted)
    flat = {
        "/".join(str(k) for k in path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]
    }
    assert set(flat) == set(param_keys)
    for k in param_keys:
        np.testing.assert_allclose(
            w0[k], flat[k], rtol=1e-5, atol=1e-6, err_msg=k
        )
    np.testing.assert_allclose(
        w0["loss_sum"], np.asarray(metrics["loss"][0]), rtol=1e-5
    )

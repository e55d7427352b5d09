"""Sharded multi-host checkpointing.

The reference saves/loads sharded parameter state where it lives
(pserver-side loadValueVector/saveValueVector,
/root/reference/paddle/pserver/ParameterServer2.cpp:1150-1213); SURVEY §5
calls the orbax-style sharded checkpoint a required upgrade. These tests
run a REAL two-process mesh (data=4,model=2) with a fully-sharded
embedding table — the configuration whose save crashed before (np.asarray on a
cross-host shard) — and assert:

- every process writes only the shards it owns; process 0 merges the
  index (no full-array npz, no cross-host materialization)
- reload with the current-mesh shardings round-trips bit-exactly and the
  restored state drives another training step
- the sharded checkpoint re-shards onto a DIFFERENT layout: this
  single-process test assembles it to host numpy and matches a
  single-process reference run
"""

import json
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROVIDERS = os.path.join(REPO, "tests", "providers")

WORKER = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "").replace("--xla_force_host_platform_device_count=8", "")
    + " --xla_force_host_platform_device_count=4"
).strip()
sys.path.insert(0, {repo!r})
sys.path.insert(0, {providers!r})
import jax
jax.config.update("jax_platforms", "cpu")
import jax._src.xla_bridge as _xb
for _n in list(_xb._backend_factories):
    if _n not in ("cpu", "tpu"):
        del _xb._backend_factories[_n]

pid = int(sys.argv[1])
jax.distributed.initialize(coordinator_address="localhost:" + sys.argv[2],
                           num_processes=2, process_id=pid)
assert len(jax.devices()) == 8

import numpy as np
from paddle_tpu.config import parse_config
from paddle_tpu.trainer import Trainer, checkpoint as ckpt
from paddle_tpu.parallel.spmd import checkpoint_sharding_fn
from paddle_tpu.utils.flags import FLAGS

ws = sys.argv[3]
FLAGS.save_dir = os.path.join(ws, "model")
FLAGS.mesh_shape = "data=4,model=2"
FLAGS.log_period = 0
FLAGS.seed = 11
trainer = Trainer(parse_config(os.path.join(ws, "cfg.py")))
trainer.train(num_passes=1)

# --- reload the saved pass with current-mesh shardings; must round-trip
# bit-exactly against the live state on every process
path = os.path.join(FLAGS.save_dir, ckpt.PASS_FMT % 0)
fn = checkpoint_sharding_fn(trainer._mesh, trainer.gm)
params2, opt2, meta = ckpt.load_checkpoint(
    path, trainer.opt_state, expected_params=trainer.params, sharding_for=fn)
for name in trainer.params:
    live = trainer.params[name]
    back = params2[name]
    assert back.sharding.is_equivalent_to(live.sharding, live.ndim), name
    for s1, s2 in zip(live.addressable_shards, back.addressable_shards):
        np.testing.assert_array_equal(np.asarray(s1.data), np.asarray(s2.data),
                                      err_msg=name)
for name, d in trainer.opt_state.slots.items():
    for slot, arr in d.items():
        for s1, s2 in zip(arr.addressable_shards, opt2.slots[name][slot].addressable_shards):
            np.testing.assert_array_equal(np.asarray(s1.data), np.asarray(s2.data),
                                          err_msg=name + "/" + slot)
assert int(opt2.step) == int(trainer.opt_state.step)

# --- the restored state must drive the sharded train step
trainer.params, trainer.opt_state = params2, opt2
provider = trainer._provider(for_test=False)
from paddle_tpu.parallel.spmd import globalize_batch
import jax.numpy as jnp
batch = globalize_batch(next(iter(provider.batches())), trainer._mesh)
trainer.params, trainer.opt_state, loss, *_ = trainer.train_step(
    trainer.params, trainer.opt_state, batch, jax.random.PRNGKey(0),
    jnp.asarray(64.0))
assert np.isfinite(float(loss))
print("WORKER_OK", pid, flush=True)
"""


def _write_config(ws):
    train_list = os.path.join(ws, "train.list")
    with open(train_list, "w") as f:
        f.write("1\n2\n")
    src = textwrap.dedent(f"""
    from paddle_tpu.trainer_config_helpers import *
    define_py_data_sources2(train_list={train_list!r}, test_list=None,
                            module="synthetic_bow", obj="process_seq")
    settings(batch_size=64, learning_rate=0.05)
    word = data_layer(name="word", size=100)
    # fully sharded table (rows over 'model', cols over 'data' — the
    # FSDP-style layout): its replica-0 shards live on BOTH processes
    emb = embedding_layer(input=word, size=16,
                          param_attr=ParamAttr(name="emb", sharding=("model", "data")))
    pool = pooling_layer(input=emb)
    output = fc_layer(input=pool, size=2, act=SoftmaxActivation(), name="output")
    label = data_layer(name="label", size=2)
    outputs(classification_cost(input=output, label=label))
    """)
    path = os.path.join(ws, "cfg.py")
    with open(path, "w") as f:
        f.write(src)
    return path


def _free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(scope="module")
def two_proc_ckpt(tmp_path_factory):
    """Run the two-process training+save+reload worker once; return ws.

    Skips (capability probe, not a failure) where the backend cannot run
    cross-process device computations — the worker TRAINS across the
    process pair, which the CPU backend refuses to compile. The sharded
    checkpoint protocol itself is host-side and stays covered everywhere
    by tests/test_elastic_ckpt.py's two-process round-trips."""
    import mp_harness

    mp_harness.skip_unless_cross_process_computations()
    ws = str(tmp_path_factory.mktemp("shardckpt"))
    _write_config(ws)
    port = _free_port()
    worker_py = os.path.join(ws, "worker.py")
    with open(worker_py, "w") as f:
        f.write(WORKER.format(repo=REPO, providers=PROVIDERS))
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, worker_py, str(i), str(port), ws],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))
    for rc, out, err in outs:
        assert rc == 0, err[-3000:]
        assert "WORKER_OK" in out, (out, err[-2000:])
    return ws


def test_sharded_layout_on_disk(two_proc_ckpt):
    """Both processes wrote shard files; index merged; no monolithic npz."""
    path = os.path.join(two_proc_ckpt, "model", "pass-00000")
    files = sorted(os.listdir(path))
    assert "params.index.json" in files, files
    assert "params.shard00000.npz" in files and "params.shard00001.npz" in files, files
    assert "params.npz" not in files  # nothing materialized whole
    assert not any(f.startswith("params.index.0") for f in files)  # partials merged
    with open(os.path.join(path, "params.index.json")) as f:
        index = json.load(f)
    # the model-sharded embedding has shards in BOTH processes' files
    emb_files = {rec["file"] for rec in index["emb"]["shards"]}
    assert emb_files == {"params.shard00000.npz", "params.shard00001.npz"}, emb_files
    assert index["emb"]["shape"] == [100, 16]
    # replicated fc weight is stored exactly once
    w = index["_output.w0"]
    starts = [tuple(r["start"]) for r in w["shards"]]
    assert starts == [(0, 0)], starts


def test_sharded_ckpt_reshards_to_single_process(two_proc_ckpt):
    """Assemble the 2-process checkpoint on this (single-process, 8-device)
    host and match a single-process reference run of the same config."""
    sys.path.insert(0, PROVIDERS)
    from paddle_tpu.config import parse_config
    from paddle_tpu.trainer import Trainer, checkpoint as ckpt
    from paddle_tpu.utils.flags import FLAGS

    FLAGS.save_dir = os.path.join(two_proc_ckpt, "ref_model")
    FLAGS.mesh_shape = "data=4,model=2"
    FLAGS.log_period = 0
    FLAGS.seed = 11
    try:
        ref = Trainer(parse_config(os.path.join(two_proc_ckpt, "cfg.py")))
        ref.train(num_passes=1)
    finally:
        sys.path.remove(PROVIDERS)

    path = os.path.join(two_proc_ckpt, "model", "pass-00000")
    params, opt_state, meta = ckpt.load_checkpoint(path, ref.opt_state,
                                                   expected_params=ref.params)
    assert meta["format_version"] == 2
    for name, ref_v in ref.params.items():
        np.testing.assert_allclose(
            np.asarray(ref_v), np.asarray(params[name]), rtol=2e-4, atol=1e-5,
            err_msg=name,
        )
    assert int(opt_state.step) == int(ref.opt_state.step)


def test_merge_model_reads_sharded_checkpoint(two_proc_ckpt, tmp_path):
    """merge_model bundles a sharded (format-2) checkpoint into one
    deployable npz — assembled values equal the shard contents."""
    from paddle_tpu.trainer import checkpoint as ckpt

    out = str(tmp_path / "merged.npz")
    ckpt.merge_model(os.path.join(two_proc_ckpt, "model"), 0, '{"m":1}', out)
    with np.load(out) as z:
        assert "__config_json__" in z.files
        merged = {k: z[k] for k in z.files if k != "__config_json__"}
    raw = ckpt._load_tree_numpy(
        os.path.join(two_proc_ckpt, "model", "pass-00000"), "params"
    )
    assert set(merged) == set(raw)
    for k in raw:
        np.testing.assert_array_equal(merged[k], raw[k], err_msg=k)


def test_streaming_restore_reads_only_overlapping_shards(tmp_path):
    """The streaming restore claim (reference block-wise semantics,
    ParameterServer2.cpp:1150-1213): assembling one device slice of a
    model-sharded 1M-row table reads ONLY the shard records overlapping
    it — O(shard bytes), never O(table bytes) — and a full restore reads
    each record exactly once (no per-device decompression amplification,
    including under full replication)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.trainer import checkpoint as ckpt

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("model",))
    rows, cols = 1_000_000, 8
    sh = NamedSharding(mesh, P("model", None))
    table = jax.device_put(
        (jnp.arange(rows, dtype=jnp.float32)[:, None] % 997.0)
        * jnp.ones((1, cols), jnp.float32),
        sh,
    )
    path = str(tmp_path)
    ckpt._save_tree_sharded(path, "params", {"table": table})
    ckpt._merge_tree_indexes(path, "params")

    table_bytes = rows * cols * 4
    shard_rows = rows // 8
    shard_bytes = shard_rows * cols * 4

    # one device slice costs one record, not the table
    reader = ckpt._ShardedTreeReader(path, ckpt._tree_index(path, "params"))
    got = reader.read_slice(
        "table", (slice(shard_rows, 2 * shard_rows), slice(None)),
        (rows, cols), np.float32,
    )
    np.testing.assert_array_equal(
        got, np.asarray(table[shard_rows : 2 * shard_rows]))
    assert reader.bytes_read == shard_bytes, (reader.bytes_read, shard_bytes)
    reader.close()

    # full sharded restore: every record read exactly once, bit-exact
    stats = {}
    params, _, _ = ckpt.load_checkpoint(
        path, sharding_for=lambda base, key, shape: sh, io_stats=stats)
    assert stats["params"] == table_bytes, stats
    np.testing.assert_array_equal(np.asarray(params["table"]), np.asarray(table))

    # fully-replicated restore must not amplify reads across the 8 devices
    rep = NamedSharding(mesh, P(None, None))
    stats2 = {}
    params2, _, _ = ckpt.load_checkpoint(
        path, sharding_for=lambda base, key, shape: rep, io_stats=stats2)
    assert stats2["params"] == table_bytes, stats2
    np.testing.assert_array_equal(np.asarray(params2["table"]), np.asarray(table))


def test_streaming_restore_cross_alignment(tmp_path):
    """A requested slice that is NOT aligned to the written shard records
    (cross-layout restore: different mesh on load) assembles from partial
    overlaps of exactly the records it intersects."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.trainer import checkpoint as ckpt

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("model",))
    rows, cols = 4096, 4
    table = jax.device_put(
        jnp.arange(rows * cols, dtype=jnp.float32).reshape(rows, cols),
        NamedSharding(mesh, P("model", None)),  # 8 records of 512 rows
    )
    path = str(tmp_path)
    ckpt._save_tree_sharded(path, "params", {"t": table})
    ckpt._merge_tree_indexes(path, "params")

    reader = ckpt._ShardedTreeReader(path, ckpt._tree_index(path, "params"))
    # rows [700, 1900) span records 1..3 with partial overlap on both ends
    got = reader.read_slice("t", (slice(700, 1900), slice(None)),
                            (rows, cols), np.float32)
    np.testing.assert_array_equal(got, np.asarray(table[700:1900]))
    # exactly records 1,2,3 were read (512 rows * 4 cols * 4 bytes each)
    assert reader.bytes_read == 3 * 512 * cols * 4, reader.bytes_read
    reader.close()

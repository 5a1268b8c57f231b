"""Checkpoint save/load roundtrips: params, optimizer state, iteration."""

import io

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bpe_transformer_tpu.checkpointing import load_checkpoint, save_checkpoint
from bpe_transformer_tpu.models import TS_TEST_CONFIG, forward, init_params
from bpe_transformer_tpu.optim import adamw_init, adamw_update


@jax.jit
def _train_step(params, state, ids):
    """One compiled update (an eager grad + AdamW dispatches — and
    compiles — every op of the model one by one, three times over)."""
    def loss_fn(p):
        return forward(p, ids, TS_TEST_CONFIG).mean()

    return adamw_update(params, jax.grad(loss_fn)(params), state, lr=1e-3)


def _train_a_bit(params, state, steps=3):
    ids = jnp.zeros((2, 8), dtype=jnp.int32)
    for _ in range(steps):
        params, state = _train_step(params, state, ids)
    return params, state


def _assert_trees_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        a,
        b,
    )


def test_checkpoint_roundtrip_path(tmp_path):
    params = init_params(jax.random.PRNGKey(0), TS_TEST_CONFIG)
    state = adamw_init(params)
    params, state = _train_a_bit(params, state)

    path = tmp_path / "ckpt.pkl"
    save_checkpoint(path, params=params, opt_state=state, iteration=3)
    payload = load_checkpoint(path)

    assert payload["iteration"] == 3
    _assert_trees_equal(payload["params"], params)
    _assert_trees_equal(payload["opt_state"], state)


def test_checkpoint_roundtrip_filelike():
    params = {"w": jnp.arange(6.0).reshape(2, 3)}
    state = adamw_init(params)
    buf = io.BytesIO()
    save_checkpoint(buf, params=params, opt_state=state, iteration=17)
    buf.seek(0)
    payload = load_checkpoint(buf)
    assert payload["iteration"] == 17
    _assert_trees_equal(payload["params"], params)


def test_checkpoint_resume_continues_identically(tmp_path):
    """Train 3 steps, checkpoint, train 3 more; reload + 3 must match."""
    params = init_params(jax.random.PRNGKey(1), TS_TEST_CONFIG)
    state = adamw_init(params)
    params, state = _train_a_bit(params, state, steps=3)
    save_checkpoint(tmp_path / "mid.pkl", params=params, opt_state=state, iteration=3)

    final_params, _ = _train_a_bit(params, state, steps=3)

    payload = load_checkpoint(tmp_path / "mid.pkl")
    from bpe_transformer_tpu.optim.adamw import AdamWState

    restored_state = AdamWState(*payload["opt_state"])
    resumed_params, _ = _train_a_bit(payload["params"], restored_state, steps=3)
    _assert_trees_equal(final_params, resumed_params)


def test_checkpoint_extra_metadata(tmp_path):
    save_checkpoint(
        tmp_path / "c.pkl",
        params={"w": jnp.ones(2)},
        iteration=5,
        extra={"val_loss": 1.25, "config": {"d_model": 64}},
    )
    payload = load_checkpoint(tmp_path / "c.pkl")
    assert payload["extra"]["val_loss"] == 1.25
    assert payload["opt_state"] is None


# ----------------------------------------------- sharded directory format


def _fsdp_state():
    from bpe_transformer_tpu.parallel import make_mesh, shard_params

    mesh = make_mesh({"data": 8})
    params = init_params(jax.random.PRNGKey(0), TS_TEST_CONFIG)
    params = shard_params(params, mesh, "fsdp")
    state = adamw_init(params)
    return mesh, params, state


def test_sharded_checkpoint_roundtrip_fsdp(tmp_path):
    """An fsdp-sharded train state round-trips through the streaming
    directory format: per-shard files on disk (never one full-tree buffer),
    exact values back."""
    from bpe_transformer_tpu.checkpointing import (
        load_checkpoint_sharded,
        save_checkpoint_sharded,
    )

    _, params, state = _fsdp_state()
    ckpt = tmp_path / "step_8.ckpt"
    save_checkpoint_sharded(ckpt, params=params, opt_state=state, iteration=8)

    # The directory really is per-shard: sharded leaves produced multiple
    # .npy files, and no pickle holds array data (treedef.pkl is structure
    # only — far smaller than the parameters).
    import json

    manifest = json.loads((ckpt / "manifest.json").read_text())
    sharded_leaves = [r for r in manifest["leaves"] if "shards" in r]
    assert sharded_leaves, "no leaf was saved shard-wise"
    assert len(list(ckpt.glob(f"{sharded_leaves[0]['name']}.*.npy"))) > 1
    param_bytes = sum(
        np.prod(r["shape"], dtype=np.int64) * 4 for r in manifest["leaves"]
    )
    assert (ckpt / "treedef.pkl").stat().st_size < param_bytes // 10

    payload = load_checkpoint_sharded(ckpt)
    assert payload["iteration"] == 8
    _assert_trees_equal(payload["params"], params)
    _assert_trees_equal(payload["opt_state"], state)


def test_sharded_checkpoint_resume_replacement(tmp_path):
    """Loading with a shardings tree places every leaf straight onto its
    mesh sharding (resume re-placement), and load_checkpoint auto-detects
    the directory format."""
    from bpe_transformer_tpu.checkpointing import (
        load_checkpoint_sharded,
        save_checkpoint_sharded,
    )
    from bpe_transformer_tpu.parallel.sharding import param_shardings

    mesh, params, state = _fsdp_state()
    ckpt = tmp_path / "ck.ckpt"
    save_checkpoint_sharded(ckpt, params=params, opt_state=state, iteration=1)

    shardings = {
        "params": param_shardings(params, mesh, "fsdp"),
        "opt_state": type(state)(
            step=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
            m=param_shardings(state.m, mesh, "fsdp"),
            v=param_shardings(state.v, mesh, "fsdp"),
        ),
    }
    payload = load_checkpoint_sharded(ckpt, shardings=shardings)
    leaf = payload["params"]["token_embeddings"]
    assert isinstance(leaf, jax.Array)
    assert leaf.sharding == shardings["params"]["token_embeddings"]
    _assert_trees_equal(payload["params"], params)

    auto = load_checkpoint(ckpt)
    assert auto["iteration"] == 1
    _assert_trees_equal(auto["params"], params)


def test_loop_fsdp_uses_sharded_checkpoints_and_resumes(tmp_path):
    """The training loop writes directory checkpoints under fsdp and resumes
    from them bit-exactly."""
    from bpe_transformer_tpu.models.config import ModelConfig
    from bpe_transformer_tpu.training.loop import LoopConfig, train
    from bpe_transformer_tpu.training.train_step import TrainHParams

    cfg = ModelConfig(
        vocab_size=256, context_length=16, d_model=32,
        num_layers=2, num_heads=2, d_ff=64,
    )
    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size, size=10_000, dtype=np.int32)
    loop_kwargs = dict(
        batch_size=8, log_every=2, eval_every=1000,
        parallel="fsdp", mesh_axes={"data": 8},
        checkpoint_dir=str(tmp_path / "ckpts"),
    )
    hp = TrainHParams(warmup_iters=2, cosine_cycle_iters=20)

    train(cfg, hp, LoopConfig(steps=4, checkpoint_every=4, **loop_kwargs),
          train_data=data, log_fn=lambda *_: None)
    ckpt = tmp_path / "ckpts" / "step_00000004.ckpt"
    assert ckpt.is_dir() and (ckpt / "manifest.json").exists()
    latest = tmp_path / "ckpts" / "latest.ckpt"
    assert latest.is_symlink()

    s_resumed = train(
        cfg, hp, LoopConfig(steps=8, checkpoint_every=4, **loop_kwargs),
        train_data=data, resume_from=str(latest), log_fn=lambda *_: None,
    )
    s_straight = train(
        cfg, hp,
        LoopConfig(steps=8, checkpoint_every=8, batch_size=8, log_every=2,
                   eval_every=1000, parallel="fsdp", mesh_axes={"data": 8},
                   checkpoint_dir=str(tmp_path / "ckpts2")),
        train_data=data, log_fn=lambda *_: None,
    )
    assert s_resumed["final_train_loss"] == pytest.approx(
        s_straight["final_train_loss"], rel=1e-5
    )


def test_async_checkpointer_roundtrip(tmp_path):
    """Background writes land the same bytes as sync saves, on_complete runs
    after the checkpoint exists, and wait() surfaces write errors."""
    from bpe_transformer_tpu.checkpointing import AsyncCheckpointer

    params = init_params(jax.random.PRNGKey(0), TS_TEST_CONFIG)
    state = adamw_init(params)
    saver = AsyncCheckpointer()

    seen = []
    path = tmp_path / "a.ckpt"
    saver.save(
        path, params=params, opt_state=state, iteration=5,
        on_complete=lambda: seen.append(path.exists()),
    )
    saver.wait()
    assert seen == [True]
    payload = load_checkpoint(path)
    assert payload["iteration"] == 5
    _assert_trees_equal(payload["params"], params)
    _assert_trees_equal(payload["opt_state"], state)

    # Sharded format through the same interface.
    _, sparams, sstate = _fsdp_state()
    sdir = tmp_path / "b.ckpt"
    saver.save(sdir, params=sparams, opt_state=sstate, iteration=7, sharded=True)
    saver.close()
    payload = load_checkpoint(sdir)
    assert payload["iteration"] == 7
    _assert_trees_equal(payload["params"], sparams)

    # A failing write is re-raised at the next wait(), not swallowed.
    saver.save(tmp_path / "nope" / "\0bad", params=params, iteration=1)
    with pytest.raises(BaseException):
        saver.wait()


def test_loop_async_checkpoint_resumable(tmp_path):
    """async_checkpoint=True: the final checkpoint is joined at loop exit
    and resumes bit-exact like the sync path."""
    from bpe_transformer_tpu.models.config import ModelConfig
    from bpe_transformer_tpu.training.loop import LoopConfig, train
    from bpe_transformer_tpu.training.train_step import TrainHParams

    cfg = ModelConfig(vocab_size=256, context_length=16, d_model=32,
                      num_layers=2, num_heads=2, d_ff=64)
    rng = np.random.default_rng(0)
    data = rng.integers(0, cfg.vocab_size, size=10_000, dtype=np.int32)
    hp = TrainHParams(warmup_iters=2, cosine_cycle_iters=20)
    lk = dict(batch_size=8, log_every=2, eval_every=1000,
              checkpoint_dir=str(tmp_path / "ck"), async_checkpoint=True)

    train(cfg, hp, LoopConfig(steps=4, checkpoint_every=4, **lk),
          train_data=data, log_fn=lambda *_: None)
    latest = tmp_path / "ck" / "latest.ckpt"
    assert latest.exists()

    resumed = train(cfg, hp, LoopConfig(steps=8, checkpoint_every=4, **lk),
                    train_data=data, resume_from=str(latest),
                    log_fn=lambda *_: None)
    straight = train(
        cfg, hp,
        LoopConfig(steps=8, checkpoint_every=8, batch_size=8, log_every=2,
                   eval_every=1000, checkpoint_dir=str(tmp_path / "ck2")),
        train_data=data, log_fn=lambda *_: None,
    )
    assert resumed["final_train_loss"] == pytest.approx(
        straight["final_train_loss"], rel=1e-5
    )


def test_sharded_checkpoint_reshard_to_different_mesh(tmp_path):
    """A checkpoint saved under one sharding layout loads onto ANOTHER
    (fsdp 8-way -> fsdp_tp 4x2): leaves reassemble from shard files and
    re-place onto the new mesh — elastic resharding."""
    from bpe_transformer_tpu.checkpointing import (
        load_checkpoint_sharded,
        save_checkpoint_sharded,
    )
    from bpe_transformer_tpu.parallel import make_mesh, shard_params
    from bpe_transformer_tpu.parallel.sharding import param_shardings

    _, params, state = _fsdp_state()  # fsdp over {"data": 8}
    ckpt = tmp_path / "reshard.ckpt"
    save_checkpoint_sharded(ckpt, params=params, opt_state=state, iteration=3)

    mesh2 = make_mesh({"data": 4, "model": 2})
    target = param_shardings(params, mesh2, "fsdp_tp")
    payload = load_checkpoint_sharded(
        ckpt,
        shardings={
            "params": target,
            "opt_state": type(state)(
                step=jax.sharding.NamedSharding(
                    mesh2, jax.sharding.PartitionSpec()
                ),
                m=target,
                v=target,
            ),
        },
    )
    leaf = payload["params"]["layers"][0]["attn"]["q_proj"]
    assert leaf.sharding == target["layers"][0]["attn"]["q_proj"]
    _assert_trees_equal(payload["params"], params)
    _assert_trees_equal(payload["opt_state"], state)


def test_sharded_checkpoint_incomplete_manifest_rejected(tmp_path):
    """A manifest whose shard boxes don't tile a leaf (e.g. written by one
    process of a multi-process mesh) must refuse to load rather than return
    uninitialized memory in the uncovered ranges."""
    import json

    from bpe_transformer_tpu.checkpointing import (
        load_checkpoint_sharded,
        save_checkpoint_sharded,
    )

    _, params, state = _fsdp_state()
    ckpt = tmp_path / "gap.ckpt"
    save_checkpoint_sharded(ckpt, params=params, opt_state=state, iteration=1)

    manifest = json.loads((ckpt / "manifest.json").read_text())
    victim = next(r for r in manifest["leaves"] if "shards" in r)
    victim["shards"] = victim["shards"][:-1]  # coverage gap
    (ckpt / "manifest.json").write_text(json.dumps(manifest))

    with pytest.raises(ValueError, match="cover|incomplete"):
        load_checkpoint_sharded(ckpt)


def test_sharded_checkpoint_orphan_recovery(tmp_path):
    """A hard crash inside the displace->replace window strands the old
    checkpoint in a `<name>.old*/d` sibling; loading the original path (via
    the public auto-detecting entry) must recover it, PROMOTE it back to the
    original path, and clean up the orphan."""
    from bpe_transformer_tpu.checkpointing import (
        load_checkpoint,
        save_checkpoint_sharded,
    )

    _, params, state = _fsdp_state()
    ckpt = tmp_path / "crashy.ckpt"
    save_checkpoint_sharded(ckpt, params=params, opt_state=state, iteration=7)

    displaced = tmp_path / "crashy.ckpt.old123xyz"
    displaced.mkdir()
    (displaced / ".bt_displaced").touch()  # the save machinery's marker
    (ckpt).rename(displaced / "d")  # simulate the crash window

    payload = load_checkpoint(ckpt)
    assert payload["iteration"] == 7
    _assert_trees_equal(payload["params"], params)
    assert (ckpt / "manifest.json").exists()  # promoted back into place
    assert not list(tmp_path.glob("crashy.ckpt.old*"))  # orphan reclaimed


def test_sharded_checkpoint_unmarked_old_sibling_untouched(tmp_path):
    """A user's manual `cp -r x.ckpt x.ckpt.old` backup (no ownership
    marker) must be neither deleted by a later save nor loaded as an
    orphan."""
    import shutil

    from bpe_transformer_tpu.checkpointing import save_checkpoint_sharded
    from bpe_transformer_tpu.checkpointing.checkpoint import (
        sharded_checkpoint_exists,
    )

    _, params, state = _fsdp_state()
    ckpt = tmp_path / "backed.ckpt"
    save_checkpoint_sharded(ckpt, params=params, opt_state=state, iteration=1)

    backup = tmp_path / "backed.ckpt.old"
    shutil.copytree(ckpt, backup / "d")  # user-made, no marker

    save_checkpoint_sharded(ckpt, params=params, opt_state=state, iteration=2)
    assert (backup / "d" / "manifest.json").exists()  # backup survives

    shutil.rmtree(ckpt)  # intentional delete: backup must NOT resurrect
    assert not sharded_checkpoint_exists(ckpt)
    with pytest.raises(FileNotFoundError):
        load_checkpoint(ckpt)


def test_sharded_checkpoint_failed_swap_restores_old(tmp_path, monkeypatch):
    """If the final directory swap raises, the previous checkpoint must be
    renamed back into place (not stranded in a temp sibling)."""
    import os as os_mod

    from bpe_transformer_tpu.checkpointing import (
        load_checkpoint_sharded,
        save_checkpoint_sharded,
    )

    _, params, state = _fsdp_state()
    ckpt = tmp_path / "swap.ckpt"
    save_checkpoint_sharded(ckpt, params=params, opt_state=state, iteration=1)

    real_replace = os_mod.replace

    def failing_replace(src, dst):
        if str(dst) == str(ckpt):
            raise OSError("simulated swap failure")
        return real_replace(src, dst)

    monkeypatch.setattr(os_mod, "replace", failing_replace)
    with pytest.raises(OSError, match="simulated swap failure"):
        save_checkpoint_sharded(ckpt, params=params, opt_state=state, iteration=2)
    monkeypatch.undo()

    payload = load_checkpoint_sharded(ckpt)  # the OLD checkpoint survives
    assert payload["iteration"] == 1
    _assert_trees_equal(payload["params"], params)
    # No stranded displaced copies remain.
    assert not list(tmp_path.glob("swap.ckpt.old*"))

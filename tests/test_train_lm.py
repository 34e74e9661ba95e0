"""The LM training contract (DESIGN.md §9): round-addressable data, resume
bitwise-determinism, per-round modal batches, and the mesh launch path."""
import os

import numpy as np
import pytest

from repro.configs import get_config
from repro.data import LMRoundLoader, TokenStream

BASE = ["--arch", "qwen2-0.5b", "--reduced", "--h-local", "2",
        "--clients", "2", "--batch", "2", "--seq", "32"]

# wall-clock measurements are the only log fields exempt from bitwise
# reproducibility (DESIGN.md §9)
MEASURED = ("wall_s", "tokens_per_s")


def _det(rec):
    return {k: v for k, v in rec.items() if k not in MEASURED}


# --------------------------------------------------------------------------- #
# round-addressable vectorized data
# --------------------------------------------------------------------------- #


def test_token_stream_batch_at_stateless():
    ts = TokenStream(64, seed=3)
    t5, l5 = ts.batch_at(5, 4, 16)
    ts.batch(4, 16)                      # stateful draws don't perturb it
    t5b, l5b = ts.batch_at(5, 4, 16)
    np.testing.assert_array_equal(t5, t5b)
    np.testing.assert_array_equal(l5, l5b)
    # a fresh stream with the same seed reproduces the same index
    t5c, _ = TokenStream(64, seed=3).batch_at(5, 4, 16)
    np.testing.assert_array_equal(t5, t5c)
    # different index / different seed -> different data
    assert not np.array_equal(t5, ts.batch_at(6, 4, 16)[0])
    assert not np.array_equal(t5, TokenStream(64, seed=4).batch_at(5, 4, 16)[0])
    # label alignment + vocab bounds survive the vectorized walk
    assert (t5[:, 1:] == l5[:, :-1]).all()
    assert t5.min() >= 0 and t5.max() < 64 and t5.dtype == np.int32


def test_lm_round_loader_round_addressable():
    s1, s2 = TokenStream(64, seed=3), TokenStream(64, seed=3)
    l1, l2 = LMRoundLoader(s1, 3, 2), LMRoundLoader(s2, 3, 2)
    b5 = l1.round_batch(5, 2, 16)
    assert b5["tokens"].shape == (3, 2, 2, 16)
    assert (b5["tokens"][..., 1:] == b5["labels"][..., :-1]).all()
    # pure function of (seed, r): call order / instance is irrelevant
    l2.round_batch(0, 2, 16)
    np.testing.assert_array_equal(b5["tokens"],
                                  l2.round_batch(5, 2, 16)["tokens"])
    assert not np.array_equal(b5["tokens"],
                              l1.round_batch(6, 2, 16)["tokens"])
    # clients draw distinct data within a round
    assert not np.array_equal(b5["tokens"][0], b5["tokens"][1])


# --------------------------------------------------------------------------- #
# modal (audio/vlm) batches advance per round
# --------------------------------------------------------------------------- #


def test_modal_batches_differ_across_rounds():
    from repro.launch.train import _wrap_modal
    cfg = get_config("musicgen-large", reduced=True)
    loader = LMRoundLoader(TokenStream(cfg.vocab_size, seed=0), 2, 2)
    b0 = _wrap_modal(cfg, loader.round_batch(0, 2, 16), 0, 0)
    b1 = _wrap_modal(cfg, loader.round_batch(1, 2, 16), 0, 1)
    assert b0["embeds"].shape == (2, 2, 2, 16, cfg.d_model)
    assert not np.array_equal(b0["embeds"], b1["embeds"])
    assert not np.array_equal(b0["labels"], b1["labels"])
    # same round reproduces bitwise (resume invariant)
    b0b = _wrap_modal(cfg, loader.round_batch(0, 2, 16), 0, 0)
    np.testing.assert_array_equal(b0["embeds"], b0b["embeds"])


def test_modal_vlm_batch_struct_and_seeding():
    from repro.launch.train import _wrap_modal
    cfg = get_config("internvl2-1b", reduced=True)
    P = cfg.frontend_tokens
    loader = LMRoundLoader(TokenStream(cfg.vocab_size, seed=0), 2, 2)
    b0 = _wrap_modal(cfg, loader.round_batch(0, 2, 32), 0, 0)
    b1 = _wrap_modal(cfg, loader.round_batch(1, 2, 32), 0, 1)
    # batch_struct contract: P patches + (S-P) text tokens
    assert b0["patches"].shape == (2, 2, 2, P, cfg.d_model)
    assert b0["tokens"].shape == (2, 2, 2, 32 - P)
    assert not np.array_equal(b0["patches"], b1["patches"])
    assert not np.array_equal(b0["tokens"], b1["tokens"])


# --------------------------------------------------------------------------- #
# resume bitwise-determinism through the driver
# --------------------------------------------------------------------------- #


def test_resume_bitwise_loss_state_log(tmp_path):
    """train(6) == train(3) + restore + train(3), bitwise: every
    deterministic log field, and the final checkpoint's raw bytes."""
    from repro.launch import train as train_mod
    da, db = str(tmp_path / "a"), str(tmp_path / "b")
    log_a = train_mod.main(BASE + ["--rounds", "6", "--ckpt", da,
                                   "--ckpt-every", "3"])
    train_mod.main(BASE + ["--rounds", "3", "--ckpt", db,
                           "--ckpt-every", "3"])
    log_b = train_mod.main(BASE + ["--rounds", "6", "--ckpt", db,
                                   "--ckpt-every", "3"])
    assert [l["round"] for l in log_b] == [3, 4, 5]   # only remaining rounds
    for ra, rb in zip(log_a[3:], log_b):
        assert _det(ra) == _det(rb)                   # loss/drift/... bitwise
    # final states bitwise equal: compare the checkpoint files themselves
    for fname in ("data.bin", "state.msgpack"):
        pa = os.path.join(da, "step_00000006", fname)
        pb = os.path.join(db, "step_00000006", fname)
        with open(pa, "rb") as fa, open(pb, "rb") as fb:
            assert fa.read() == fb.read(), fname


def test_data_rounds_revisits_data():
    """--data-rounds 1 trains every round on round 0's data: round 0 is the
    fresh run's round 0 bitwise, and the loss then falls every round."""
    from repro.launch import train as train_mod
    fresh = train_mod.main(BASE + ["--rounds", "1"])
    log = train_mod.main(BASE + ["--rounds", "3", "--data-rounds", "1"])
    assert _det(log[0]) == _det(fresh[0])
    losses = [r["loss"] for r in log]
    assert losses[2] < losses[1] < losses[0], losses


def test_layers_cuts_depth_and_reports_setup():
    """--layers cuts depth with widths kept; the log's setup names the
    device, the depth and the compiled round step."""
    from repro.launch import train as train_mod
    log = train_mod.main(BASE + ["--rounds", "1", "--layers", "1"])
    s = log.setup
    assert s["n_layers"] == 1
    assert s["device"]["platform"] == "cpu" and s["device"]["count"] >= 1
    assert s["compile_s"] > 0 and s["argument_bytes"] > 0
    assert s["pallas_calls"] == 0          # interpret mode on the CPU


def test_profile_dir_traces_the_rounds_after_compile(tmp_path):
    """--profile-dir writes one trace of the PROFILE_ROUNDS rounds after the
    compile round: each a step 'round' holding the four host spans."""
    import glob
    import jax
    from repro.launch import train as train_mod
    logdir = str(tmp_path / "prof")
    n = train_mod.PROFILE_ROUNDS
    log = train_mod.main(BASE + ["--rounds", str(n + 2), "--layers", "1",
                                 "--profile-dir", logdir])
    assert len(log) == n + 2
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(found) == 1
    pd = jax.profiler.ProfileData.from_file(found[0])
    names = ("round", "make_batch", "put_batch", "dispatch", "read_loss")
    seen = [e.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name in names]
    assert {name: seen.count(name) for name in names} == \
        {name: n for name in names}


@pytest.mark.slow
def test_resume_bitwise_10_rounds(tmp_path):
    """The contract at the issue's full length: train(10) == train(5)+train(5)."""
    from repro.launch import train as train_mod
    da, db = str(tmp_path / "a"), str(tmp_path / "b")
    log_a = train_mod.main(BASE + ["--rounds", "10", "--ckpt", da,
                                   "--ckpt-every", "5"])
    train_mod.main(BASE + ["--rounds", "5", "--ckpt", db,
                           "--ckpt-every", "5"])
    log_b = train_mod.main(BASE + ["--rounds", "10", "--ckpt", db,
                                   "--ckpt-every", "5"])
    assert [l["round"] for l in log_b] == list(range(5, 10))
    for ra, rb in zip(log_a[5:], log_b):
        assert _det(ra) == _det(rb)
    for fname in ("data.bin", "state.msgpack"):
        with open(os.path.join(da, "step_00000010", fname), "rb") as fa, \
                open(os.path.join(db, "step_00000010", fname), "rb") as fb:
            assert fa.read() == fb.read(), fname


# --------------------------------------------------------------------------- #
# mesh launch path (steps.build_train_step end-to-end)
# --------------------------------------------------------------------------- #


@pytest.mark.slow
def test_mesh_path_end_to_end_with_resume(tmp_path):
    """--mesh routes through steps.build_train_step (shardings + donation);
    the plan fixes M, checkpoints interoperate with the same driver loop."""
    from repro.launch import train as train_mod
    argv = ["--arch", "qwen2-0.5b", "--reduced", "--mesh", "debug",
            "--mesh-shape", "1x1", "--method", "local-adam",
            "--h-local", "2", "--batch", "2",
            "--seq", "32", "--ckpt", str(tmp_path), "--ckpt-every", "1"]
    log = train_mod.main(argv + ["--rounds", "2"])
    assert len(log) == 2
    assert all(np.isfinite(l["loss"]) for l in log)
    assert all("step_norm" in l for l in log)         # adaptive server threads
    # resume runs only the remaining round
    log2 = train_mod.main(argv + ["--rounds", "3"])
    assert [l["round"] for l in log2] == [2]


@pytest.mark.slow
def test_modal_driver_end_to_end():
    """Audio family through the driver: per-round modal batches reach the
    engine (loss varies across rounds — a frozen batch kept it fixed)."""
    from repro.launch import train as train_mod
    log = train_mod.main(["--arch", "musicgen-large", "--reduced",
                          "--rounds", "2", "--h-local", "2", "--clients", "2",
                          "--batch", "2", "--seq", "16"])
    assert len(log) == 2
    assert all(np.isfinite(l["loss"]) for l in log)

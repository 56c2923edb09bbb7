"""The launch path's rule for who may touch the chip, and chip_smoke.py.

CPU only. The driver (this pytest process) runs with JAX_PLATFORMS=cpu and the
node below advertises two FAKE chips (num_tpus=2): enough to see which
platform the raylet starts each worker on, and nothing here initialises JAX in
a TPU-leased worker. chip_smoke.py's own loop runs at gpt2_tiny size on the
CPU mesh — the repo's one test of GPT-2 through JaxTrainer and the Data
iterator; at GPT-2-124M it runs on the chip (python chip_smoke.py).
"""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------------- units
@pytest.mark.parametrize("driver_platforms", ["cpu", "tpu,cpu", None])
def test_worker_platform_comes_from_the_lease(monkeypatch, driver_platforms):
    """TPU in the demand → the process starts on `tpu`; otherwise `cpu` —
    whatever JAX_PLATFORMS the raylet inherited from the driver."""
    from ray_tpu.core.raylet import worker_pool

    if driver_platforms is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", driver_platforms)
    assert worker_pool.worker_platform({"CPU": 1, "TPU": 4}) == "tpu"
    assert worker_pool.worker_platform({"CPU": 1, "TPU": 0}) == "cpu"
    assert worker_pool.worker_platform({"CPU": 1}) == "cpu"
    assert worker_pool.worker_platform(None) == "cpu"

    spawned = []

    class FakePopen:
        pid = 0

        def __init__(self, argv, env, **_):
            spawned.append(env)

    monkeypatch.setattr(worker_pool.subprocess, "Popen", FakePopen)
    pool = worker_pool.WorkerPool(
        "127.0.0.1:1", "127.0.0.1:2", "s-unit-platform", "n0",
        env={"JAX_PLATFORMS": "tpu,cpu"},
    )
    pool.start_worker()
    pool.start_worker(actor_id=b"a", platform=worker_pool.worker_platform({"TPU": 1}))
    assert [e["JAX_PLATFORMS"] for e in spawned] == ["cpu", "tpu"]


def test_detect_tpu_resources_counts_device_files(monkeypatch):
    from ray_tpu.core import resources

    chips = ["/dev/vfio/0", "/dev/vfio/1"]
    monkeypatch.setattr(resources, "tpu_device_files", lambda: chips)
    monkeypatch.setattr(resources.os, "access", lambda p, mode: True)
    # the slice's name does not count this host's chips, the files do
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert resources.detect_tpu_resources() == {}
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert resources.detect_tpu_resources() == {"TPU": 2.0, "TPU-v5litepod": 2.0}
    monkeypatch.delenv("JAX_PLATFORMS")
    assert resources.detect_tpu_resources()["TPU"] == 2.0

    # chips that cannot be opened are a broken host, not a TPU-less one
    monkeypatch.setattr(resources.os, "access", lambda p, mode: False)
    with pytest.raises(RuntimeError, match="cannot open"):
        resources.detect_tpu_resources()
    monkeypatch.setattr(resources, "tpu_device_files", lambda: [])
    assert resources.detect_tpu_resources() == {}


def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path):
    import jax

    from ray_tpu.util import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # untouched

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(REPO, ".jax_cache")
        assert compile_cache.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_attention_call_shapes_reads_compiled_hlo():
    import chip_smoke

    hlo = "\n".join([
        "  %fusion.1 = bf16[96,1024,64]{2,1,0} fusion(%p)",
        '  %flash_attention_fwd.3 = (bf16[96,1024,64]{2,1,0:T(8,128)(2,1)}, '
        'f32[96,1,1024]{2,1,0}) custom-call(s32[1]{0} %a, s32[1]{0} %b, '
        'bf16[96,1024,64]{2,1,0} %q, bf16[96,1024,64]{2,1,0} %k), '
        'custom_call_target="tpu_custom_call", backend_config={}',
        '  %flash_attention_bwd.4 = (bf16[384,1024,64]{2,1,0}, bf16[384,1024,64]'
        '{2,1,0}) custom-call(%x), custom_call_target="tpu_custom_call"',
    ])
    assert chip_smoke.attention_call_shapes(hlo, 64) == (
        2, [[96, 1024, 64], [384, 1024, 64]]
    )
    # the S-minor pair's operands are [rows, hd, S]
    s_minor = hlo.replace("1024,64]", "64,1024]")
    assert chip_smoke.attention_call_shapes(s_minor, 64) == (
        2, [[96, 64, 1024], [384, 64, 1024]]
    )


# ------------------------------------------------------- separate processes
def _run(code_or_argv, env_update, cwd=REPO, timeout=180):
    env = {**os.environ, **{k: v for k, v in env_update.items() if v is not None}}
    for k, v in env_update.items():
        if v is None:
            env.pop(k, None)
    argv = code_or_argv if isinstance(code_or_argv, list) else [
        sys.executable, "-c", code_or_argv
    ]
    return subprocess.run(argv, env=env, cwd=cwd, capture_output=True,
                          text=True, timeout=timeout)


def test_init_with_detection_leaves_driver_off_jax_and_no_process_behind():
    """ray_tpu.init() counts chips (JAX_PLATFORMS unset, so the device-file
    path really runs) and the driver ends with no JAX backend initialised;
    after shutdown() nothing the session started is still running — the
    raylet stops its workers and transfer daemon when it is terminated."""
    code = (
        "import glob, sys, json, ray_tpu\n"
        "ray_tpu.init(num_cpus=1)\n"
        "res = ray_tpu.cluster_resources()\n"
        "session = ray_tpu.api._global_worker().backend.core.session\n"
        "ray_tpu.get(ray_tpu.remote(lambda: 1).remote(), timeout=60)\n"
        "ray_tpu.shutdown()\n"
        "left = []\n"
        "for f in glob.glob('/proc/[0-9]*/environ') + glob.glob('/proc/[0-9]*/cmdline'):\n"
        "    try:\n"
        "        if session.encode() in open(f, 'rb').read(): left.append(f)\n"
        "    except OSError: pass\n"
        "jax = sys.modules.get('jax')\n"
        "up = jax is not None and jax._src.xla_bridge.backends_are_initialized()\n"
        "print(json.dumps({'backend': up, 'TPU': res.get('TPU', 0), 'left': left}))\n"
    )
    r = _run(code, {"JAX_PLATFORMS": None})
    assert r.returncode == 0, r.stderr[-2000:]
    assert json.loads(r.stdout.strip().splitlines()[-1]) == {
        "backend": False, "TPU": 0, "left": [],
    }


def test_chip_smoke_without_a_chip_fails_and_says_why(tmp_path):
    r = _run([sys.executable, "chip_smoke.py"], {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "no TPU" in r.stderr and "JAX_PLATFORMS=cpu" in r.stderr, r.stderr
    assert '"ok"' not in r.stdout

    # and alone, without the program
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
    r = _run([sys.executable, "chip_smoke.py"], {"PYTHONPATH": None},
             cwd=str(tmp_path))
    assert r.returncode != 0 and '"ok"' not in r.stdout


# ------------------------------------------------- one node, two fake chips
@pytest.fixture(scope="module")
def fake_tpu_node():
    import ray_tpu

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=2)
    yield ray_tpu
    ray_tpu.shutdown()


def _platform():
    import os

    return os.environ.get("JAX_PLATFORMS")


def test_only_the_tpu_leased_process_starts_on_tpu(fake_tpu_node):
    ray_tpu = fake_tpu_node
    from ray_tpu import exceptions as exc

    class Holder:
        def platform(self):
            return _platform()

    leased = ray_tpu.remote(num_tpus=1)(Holder).remote()
    plain = ray_tpu.remote(Holder).remote()
    task = ray_tpu.remote(_platform)
    assert os.environ["JAX_PLATFORMS"] == "cpu"  # the driver's, not a worker's
    assert ray_tpu.get(
        [leased.platform.remote(), plain.platform.remote(), task.remote()],
        timeout=60,
    ) == ["tpu", "cpu", "cpu"]
    # a pooled task worker is on the CPU backend: it is never handed a TPU
    # lease it would silently run on the CPU
    with pytest.raises(exc.RayTpuError, match="actors only"):
        ray_tpu.get(ray_tpu.remote(num_tpus=1)(_platform).remote(), timeout=60)
    ray_tpu.kill(leased)
    ray_tpu.kill(plain)


def test_two_tpu_workers_on_one_host_are_refused_not_hung(fake_tpu_node):
    from ray_tpu.train.worker_group import WorkerGroup

    group = WorkerGroup(2, {"CPU": 1, "TPU": 1})
    try:
        with pytest.raises(RuntimeError, match="one worker per host"):
            group.rendezvous()
    finally:
        group.shutdown()


def test_gpt2_through_trainer_and_iterator_at_toy_size(fake_tpu_node):
    """chip_smoke's loop: JaxTrainer → Dataset.iter_batches(sharding=…) →
    make_gpt2_train_step → step_fn, with gpt2_tiny on the worker's CPU mesh."""
    import chip_smoke
    from ray_tpu.models import gpt2

    from ray_tpu.models import llama

    cfg, steps = gpt2.gpt2_tiny(), 16
    eva_cfg = llama.evabyte_tiny(remat=True, attention_impl="pallas")
    from ray_tpu.models import nemotron_h

    from ray_tpu.models import minicpm_sala

    hybrid_cfg = nemotron_h.nemotron_h_tiny(remat=True)
    sala_cfg = minicpm_sala.minicpm_sala_tiny(remat=True)
    from ray_tpu.models import lfm2_moe

    lfm2_cfg = lfm2_moe.lfm2_moe_tiny(remat=True)
    from ray_tpu.models import deepseek_v2

    dsv2_cfg = deepseek_v2.deepseek_v2_tiny(remat=True,
                                            attention_impl="pallas")
    rows = chip_smoke.run(cfg, steps=steps, per_chip_batch=1,
                          num_devices=8, use_tpu=False, eva_model=eva_cfg,
                          hybrid_model=hybrid_cfg, sala_model=sala_cfg,
                          lfm2_model=lfm2_cfg, dsv2_model=dsv2_cfg,
                          xing4_model=deepseek_v2.xing4_tiny(
                              remat=True, hc_sinkhorn_iters=2, n_layer=1,
                              first_layer=2),
                          grouped_shapes=(("toy", 512, 4, 400, 256, 128),))
    assert chip_smoke.check_training(rows, cfg, steps) == []
    summary = rows[-1]["summary"]
    # the grouped products (PR 60): the program's kernels (interpreted here)
    # against lax.ragged_dot at the toy's two matrix shapes, value and both
    # gradients, and the rule's decision for each form came back; and the
    # check fails on a difference or a missing decision
    grouped = summary["hybrid"]["grouped"]
    assert len(grouped["off"]) == 6 and max(grouped["off"].values()) < 2 ** -6
    assert {(d["K"], d["N"], d["form"], d["impl"]) for d in grouped["tiling"]
            if d["rows"] == 512} == {
        (k, n, form, "pallas") for k, n in ((256, 128), (128, 256))
        for form in ("gmm", "gmm_t", "tgmm")}
    wrong = [rows[-1] | {"summary": summary | {"hybrid": summary["hybrid"] | {
        "grouped": grouped | {"off": {"toy": 0.5}, "tiling": []}}}}]
    assert len(chip_smoke.check_training(rows[:-1] + wrong, cfg, steps)) == 7
    # the Xing4.0 step (PR 57): the same family with four hyper-connection
    # streams, a biased router and an MTP module — its decision event, its
    # three expert layers' loads (the MTP module's last) and what the step
    # said of them came back; and the check fails without them
    xing4 = summary["xing4"]
    assert [d["groups"] for d in xing4["layer_pattern"]] == [
        ["E"]]
    assert xing4["hyper_connection"] == [{
        "streams": 4, "rounds": 2, "stream_dtype": "bfloat16",
        "carry_bytes_per_token": 4 * 128 * 2}]
    assert [e["layer"] for e in xing4["expert_load"]] == [2, 6]
    assert np.asarray(xing4["step_load"]).shape == (2, 3)
    stripped = [rows[-1] | {"summary": summary | {"xing4": xing4 | {
        "layer_pattern": [], "hyper_connection": [], "expert_load": []}}}]
    assert len(chip_smoke.check_training(rows[:-1] + stripped, cfg, steps)
               ) == 2
    # the DeepSeek-V2 step (PR 55): its pattern, the flash kernels' tilings
    # at the two widths, its three expert layers' loads and the balance loss
    # each said beside its load came back; and the check fails without them
    dsv2 = summary["dsv2"]
    assert [d["groups"] for d in dsv2["layer_pattern"]] == [
        ["D", "3 x scan(E)"]]
    assert [e["layer"] for e in dsv2["expert_load"]] == [1, 2, 3]
    assert {(d["kernel"], d["hd"], d["hd_v"], d["layout"])
            for d in dsv2["flash_tiling"]} == {
        ("fwd", 24, 16, "s_minor"), ("bwd", 24, 16, "s_minor")}
    assert np.asarray(dsv2["step_load"]).shape == (3, 3)
    assert all(0.8 < b < 2.0 for b in dsv2["balance_loss"])
    bare = [rows[-1] | {"summary": summary | {"dsv2": dsv2 | {
        "layer_pattern": [], "remat_policy": [], "expert_load": [],
        "flash_tiling": [], "balance_loss": [0.0]}}}]
    assert len(chip_smoke.check_training(rows[:-1] + bare, cfg, steps)) == 4
    # the LFM2-MoE step (PR 50): its pattern of pairs, the rule's decision
    # over its three kinds and its four expert layers' loads came back, no
    # pair dropped; and the check fails without them
    lfm2 = summary["lfm2"]
    assert [d["groups"] for d in lfm2["layer_pattern"]] == [
        ["D", "A", "3 x scan(C)"]]
    assert [d["n_layer"] for d in lfm2["remat_policy"]] == [5]
    assert [e["layer"] for e in lfm2["expert_load"]] == [2, 3, 4, 5]
    assert all(e["pairs_dropped"] == 0 and e["tokens"] == 8 * lfm2_cfg.seq_len
               for e in lfm2["expert_load"])
    # ... and what the step itself said of them at run time (PR 52): a row
    # a layer of (passes, pairs, fullest held expert), the set-up loads' kin
    for step in (lfm2, summary["hybrid"]):
        load = np.asarray(step["step_load"])
        assert load.shape == (4, 3) and (load[:, 0] >= 1).all()
        assert (load[:, 2] <= load[:, 1]).all() and (load[:, 1] <= 4 * 8 * 64).all()
    none = [rows[-1] | {"summary": summary | {"lfm2": lfm2 | {
        "layer_pattern": [], "remat_policy": [], "expert_load": []}}}]
    assert len(chip_smoke.check_training(rows[:-1] + none, cfg, steps)) == 2
    # the MiniCPM-SALA step (PR 47): its pattern, the scan at one head a
    # group, the selection on the sparse branch and the three kernels'
    # tilings came back; and the check fails without them
    sala = summary["sala"]
    assert [d["groups"] for d in sala["layer_pattern"]] == [
        ["3 x scan(L)", "S"]]
    assert {d["kernel"] for d in sala["ssd_tiling"]} == {"fwd", "bwd"}
    assert any(d["mode"] == "sparse" and d["S"] == sala_cfg.seq_len
               for d in sala["sparse_selection"])
    assert {d["kernel"] for d in sala["sparse_tiling"]} == {
        "fwd", "bwd_dq", "bwd_dkv"}
    bare = [rows[-1] | {"summary": summary | {"sala": sala | {
        "layer_pattern": [], "ssd_tiling": [], "sparse_selection": [],
        "sparse_tiling": []}}}]
    assert len(chip_smoke.check_training(rows[:-1] + bare, cfg, steps)) == 4
    # the hybrid step: both of its events came back, no pair dropped; and
    # the check fails without them
    hybrid = summary["hybrid"]
    assert {d["pattern"] for d in hybrid["layer_pattern"]} >= {
        hybrid_cfg.pattern, hybrid_cfg.mtp_pattern}
    assert [e["layer"] for e in hybrid["expert_load"]] == [0, 1, 2, 3]
    assert all(e["pairs_dropped"] == 0 and e["tokens"] == 8 * hybrid_cfg.seq_len
               for e in hybrid["expert_load"])
    # how full the passes that ran were rides along (PR 36)
    assert all(e["buffer_fill"] == pytest.approx(
        e["pairs"] / (e["buffer_passes"] * e["buffer_rows"]))
        for e in hybrid["expert_load"])
    # its scan's kernel pair (PR 41), interpreted a row a device under the
    # fsdp=8 shard_map, left its tiling decisions
    assert {d["kernel"] for d in hybrid["ssd_tiling"]} == {"fwd", "bwd"}
    assert all((d["rows"], d["S"], d["Q"]) == (1, hybrid_cfg.seq_len,
                                               hybrid_cfg.chunk)
               for d in hybrid["ssd_tiling"])
    without = [rows[-1] | {"summary": summary | {"hybrid": hybrid | {
        "layer_pattern": [], "expert_load": [], "ssd_tiling": []}}}]
    assert len(chip_smoke.check_training(rows[:-1] + without, cfg, steps)) == 3
    # the chosen set as a mask is top_k's own list on this backend, ties
    # included; and the check fails where it is not
    assert hybrid["chosen_rows_off"] == 0
    tied = [rows[-1] | {"summary": summary | {"hybrid": hybrid | {
        "chosen_rows_off": 3}}}]
    assert len(chip_smoke.check_training(rows[:-1] + tied, cfg, steps)) == 1
    # the EVA step: interpreted kernels under the fsdp=8 shard_map, a row a
    # device, with their tiling decisions and the rule's
    assert summary["eva"]["attention"] == ["pallas", True]
    assert {d["kernel"] for d in summary["eva"]["tiling"]} == {"fwd", "bwd"}
    assert all(d["rows"] == eva_cfg.n_head for d in summary["eva"]["tiling"])
    # its heads' loss went in chunks that made their gradient (PR 39); and
    # the check fails without the event
    (head,) = summary["eva"]["head_loss"]
    assert head["grad_in_forward"] and head["heads"] == eva_cfg.n_pred_heads
    assert head["rows"] * head["chunks"] == eva_cfg.seq_len
    unsaid = [rows[-1] | {"summary": summary | {"eva": summary["eva"] | {
        "head_loss": []}}}]
    assert len(chip_smoke.check_training(rows[:-1] + unsaid, cfg, steps)) == 1
    assert summary["platforms"] == ["cpu"] and summary["device_count"] == 8
    assert summary["mesh"] == {"fsdp": 8} and summary["global_batch"] == 8
    assert summary["attention"] == ["xla", True]   # today's CPU-mesh choice
    assert summary["cache_dir"] == os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jax_cache")
    )
    # and what the chip check would say about this run
    assert chip_smoke.check_device(summary, cfg, 1, advertised_tpus=8) != []


def test_qwen3_next_through_trainer_at_toy_size(fake_tpu_node):
    """chip_smoke's loop with the small Qwen3-Next step alone beside GPT-2's
    (PR 61; a test of its own: with it the one above passed its time limit
    under the suite's six workers)."""
    import chip_smoke
    from ray_tpu.models import gpt2, qwen3_next

    cfg, steps = gpt2.gpt2_tiny(), 16
    rows = chip_smoke.run(cfg, steps=steps, per_chip_batch=1, num_devices=8,
                          use_tpu=False,
                          qwen3_model=qwen3_next.qwen3_next_tiny(
                              remat=True, attention_impl="pallas"),
                          grouped_shapes=())
    assert chip_smoke.check_training(rows, cfg, steps) == []
    summary = rows[-1]["summary"]
    # its pattern, the delta rule's three kernels' tilings (the solve's, the
    # forward's, the backward's: both value heads of a key head a grid step),
    # the flash pair at its head width, its four layers' loads and balance
    # losses came back; and the check fails without them — or with the pair's
    # decisions alone: a step that solved inside the other two
    qwen3 = summary["qwen3"]
    assert [d["groups"] for d in qwen3["layer_pattern"]] == [
        ["3 x scan(L)", "F"]]
    assert {(d["kernel"], d["C"], d["head_tile"])
            for d in qwen3["delta_tiling"]} == {
        ("solve", 16, 2), ("fwd", 16, 2), ("bwd", 16, 2)}
    # ... and (PR 63) the four kernels around the scan: q and k under the
    # norm over 2 heads, v without, o and z over 4
    assert {(d["kernel"], d["heads"]) for d in qwen3["pointwise_tiling"]} == {
        ("conv_norm_fwd", 2), ("conv_norm_bwd", 2), ("conv_norm_fwd", 0),
        ("conv_norm_bwd", 0), ("gate_norm_fwd", 4), ("gate_norm_bwd", 4)}
    assert [e["layer"] for e in qwen3["expert_load"]] == [0, 1, 2, 3]
    assert np.asarray(qwen3["step_load"]).shape == (4, 3)
    assert all(0.8 < b < 2.0 for b in qwen3["balance_loss"])
    no_kernel = [rows[-1] | {"summary": summary | {"qwen3": qwen3 | {
        "delta_tiling": [], "layer_pattern": [], "expert_load": []}}}]
    assert len(chip_smoke.check_training(rows[:-1] + no_kernel, cfg, steps)
               ) == 3
    no_gate = [rows[-1] | {"summary": summary | {"qwen3": qwen3 | {
        "pointwise_tiling": [d for d in qwen3["pointwise_tiling"]
                             if d["kernel"] != "gate_norm_bwd"]}}}]
    assert len(chip_smoke.check_training(rows[:-1] + no_gate, cfg, steps)
               ) == 1
    no_solve = [rows[-1] | {"summary": summary | {"qwen3": qwen3 | {
        "delta_tiling": [d for d in qwen3["delta_tiling"]
                         if d["kernel"] != "solve"]}}}]
    assert len(chip_smoke.check_training(rows[:-1] + no_solve, cfg, steps)
               ) == 1


def test_ouro_through_trainer_at_toy_size(fake_tpu_node):
    """chip_smoke's loop with a small Ouro step (two layers run twice, a head
    and an exit gate after each pass) alone beside GPT-2's (PR 64; a test of
    its own with its own time limit, as PR 61's lesson has it)."""
    import chip_smoke
    from ray_tpu.models import gpt2, llama

    cfg, steps = gpt2.gpt2_tiny(), 16
    rows = chip_smoke.run(cfg, steps=steps, per_chip_batch=1, num_devices=8,
                          use_tpu=False,
                          ouro_model=llama.ouro_tiny(ut_steps=2, remat=True),
                          grouped_shapes=())
    assert chip_smoke.check_training(rows, cfg, steps) == []
    summary = rows[-1]["summary"]
    ouro = summary["ouro"]
    assert [(d["passes"], d["layers"], d["applications"])
            for d in ouro["loop"]] == [(2, 2, 4)]
    assert "2 x 8 rows" in ouro["loop"][0]["heads"]
    assert [(d["n_layer"], d["passes"], d["applications"])
            for d in ouro["remat_policy"]] == [(2, 2, 4)]
    p1, p2, entropy = ouro["exit_distribution"]
    assert p1 + p2 == pytest.approx(1.0, abs=1e-5) and 0.4 < p1 < 0.6
    assert 0.6 < entropy < math.log(2) + 1e-5
    # the check fails unless the model/loop event was recorded
    no_loop = [rows[-1] | {"summary": summary | {"ouro": ouro | {
        "loop": []}}}]
    bad = chip_smoke.check_training(rows[:-1] + no_loop, cfg, steps)
    assert len(bad) == 1 and "model/loop" in bad[0]


def test_afmoe_through_trainer_at_toy_size(fake_tpu_node):
    """chip_smoke's loop with a small AFMoE step (window and full layers in
    one pattern, experts beside a shared one) alone beside GPT-2's (PR 66; a
    test of its own with its own time limit, as PR 61's lesson has it): the
    interpreted flash pair records a windowed and a full call of each
    kernel, and the check fails without the windowed ones."""
    import chip_smoke
    from ray_tpu.models import afmoe, gpt2

    cfg, steps = gpt2.gpt2_tiny(), 16
    toy = afmoe.afmoe_tiny(remat=True, attention_impl="pallas", seq_len=128,
                           head_dim=32)
    rows = chip_smoke.run(cfg, steps=steps, per_chip_batch=1, num_devices=8,
                          use_tpu=False, afmoe_model=toy, grouped_shapes=())
    assert chip_smoke.check_training(rows, cfg, steps) == []
    summary = rows[-1]["summary"]
    step = summary["afmoe"]
    assert [d["groups"] for d in step["layer_pattern"]] == [
        ["D", "W", "F", "2 x scan(W)"]]
    assert {(d["kernel"], d["window"]) for d in step["flash_tiling"]} == {
        (k, w) for k in ("fwd", "bwd") for w in (0, toy.sliding_window)}
    assert [load["layer"] for load in step["expert_load"]] == [2, 3, 4, 5]
    full_only = [rows[-1] | {"summary": summary | {"afmoe": step | {
        "flash_tiling": [d for d in step["flash_tiling"]
                         if not d["window"]]}}}]
    bad = chip_smoke.check_training(rows[:-1] + full_only, cfg, steps)
    assert len(bad) == 1 and "ops/flash_tiling" in bad[0]
    # a windowed call that visits the whole triangle is refused
    whole = [rows[-1] | {"summary": summary | {"afmoe": step | {
        "flash_tiling": [d | {"Skv": 4096, "tiles_visited": 36,
                              "tiles_causal": 36} if d["window"] else d
                         for d in step["flash_tiling"]]}}}]
    bad = chip_smoke.check_training(rows[:-1] + whole, cfg, steps)
    assert len(bad) == 2 and all("band" in b for b in bad)


def test_step_load_line_finds_the_steps_own_event_or_fails():
    """chip_smoke reads a toy expert step's run-time load from the session's
    record — the `train/step_counters` event whose rows are the step's own
    `metrics["counters"]` — and fails where the record holds none."""
    import chip_smoke

    step = {"step_load": [[1, 130, 20], [2, 300, 90]]}
    event = {"cat": "train", "name": "step_counters", "ph": "i", "ts": 12.5e6,
             "args": {"step": 1, "kind": "expert_load", "t_dispatch": 12.0,
                      "layers": [2, 3], "passes": [1, 2], "pairs": [130, 300],
                      "max_per_expert": [20, 90], "buffer_rows": 256,
                      "held": 8}}
    other = {**event, "args": {**event["args"], "pairs": [131, 300]}}
    lines, bad = chip_smoke.step_load_line([other, event], step, "toy")
    assert not bad and len(lines) == 1
    assert "layers [2, 3] ran [1, 2] pass(es)" in lines[0]
    assert "0.50 s after its dispatch" in lines[0]
    lines, bad = chip_smoke.step_load_line([other], step, "toy")
    assert not lines and len(bad) == 1 and "train/step_counters" in bad[0]


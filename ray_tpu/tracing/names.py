"""The names the Train path gives its own work — one vocabulary, written once.

The model (``models/``), the step factories (``train/train_step.py``) and the
kernels (``ops/attention.py``) put these names on the device through
``jax.named_scope`` / ``pallas_call(name=...)``; Data and Train put their span
names on the profiler's clock through ``profile_span``. Readers of a trace
(``benchmarks/harness/program_trace.py``), ``tests/test_train_tracing.py`` and
``PERF.md`` use the same tuples. Constants only: importing this costs nothing.

On the device a scope shows in each HLO instruction's ``op_name`` (the
trace's ``tf_op``): ``jit(step)/jvp()/while/body/closed_call/block/mlp/tanh``
is forward, ``jit(step)/transpose(jvp())/.../block/mlp/dot_general`` backward,
``.../checkpoint/rematted_computation/block/...`` remat's recompute,
``jit(step)/optimizer/mul`` the update.
"""

# scopes inside one transformer block, all nested under BLOCK
BLOCK = "block"
LN1, QKV, ATTN, PROJ, LN2, MLP = "ln1", "qkv", "attn", "proj", "ln2", "mlp"
MOE = "moe"                      # stands where `mlp` stands on the MoE branch
BLOCK_SCOPES = (LN1, QKV, ATTN, PROJ, LN2, MLP)
# the rest of the model, and the step
EMBED = "embed"
LN_F = "ln_f"
LM_HEAD_LOSS = "lm_head_loss"
OPTIMIZER = "optimizer"
# the public entry points of ops/attention.py (carried by the shard_map too)
FLASH_ATTENTION = "flash_attention"
SCOPES = (EMBED, BLOCK) + BLOCK_SCOPES + (MOE, LN_F, LM_HEAD_LOSS, OPTIMIZER,
                                          FLASH_ATTENTION)

# the two Mosaic kernels (`name=` of their pallas_call)
FLASH_FWD_KERNEL = "flash_attention_fwd"
FLASH_BWD_KERNEL = "flash_attention_bwd"
KERNELS = (FLASH_FWD_KERNEL, FLASH_BWD_KERNEL)
# the tiling ops/attention.py chose for a kernel: one instant event per
# distinct decision, at trace time, in the task-event buffer
FLASH_TILING = "ops/flash_tiling"
FLASH_TILING_ARGS = ("kernel", "rows", "Sq", "Skv", "hd", "block_q", "block_k",
                     "vmem_estimate")

# the block's residuals a `remat=True` checkpoint may keep, one name a tensor
# (`jax.ad_checkpoint.checkpoint_name`; an identity outside such a checkpoint):
# the three qkv einsums' outputs, the flash forward kernel's output and
# logsumexp (tagged in ops/attention._flash_fwd, where lse exists), x after
# the attention residual add, the MLP's hidden pre-activation
RES_Q, RES_K, RES_V = "block_q", "block_k", "block_v"
RES_FLASH_O, RES_FLASH_LSE = "flash_o", "flash_lse"
RES_MID = "block_mid"
RES_MLP_HIDDEN = "mlp_hidden"
RESIDUALS = (RES_Q, RES_K, RES_V, RES_FLASH_O, RES_FLASH_LSE, RES_MID,
             RES_MLP_HIDDEN)
# which of them models/gpt2.py chose to save: one instant event per distinct
# decision, at trace time, in the task-event buffer
REMAT_POLICY = "model/remat_policy"
REMAT_POLICY_ARGS = ("n_layer", "batch", "seq", "saved", "saved_bytes",
                     "budget_bytes", "bytes_limit")

# host spans: `ray_tpu:<component>/<name>` on the profiler's clock,
# `<component>/<name>` with that component in the task-event buffer
SPAN_PREFIX = "ray_tpu:"
DATA_GET_BLOCK = "data/get_block"
DATA_ASSEMBLE = "data/assemble"
DATA_DEVICE_PUT = "data/device_put"
TRAIN_REPORT = "train/report"
TRAIN_POLL = "train/poll"
GC = "gc"
BG = "bg"                        # `bg/<loop>`: one span a tick of a periodic loop
SPANS = (DATA_GET_BLOCK, DATA_ASSEMBLE, DATA_DEVICE_PUT, TRAIN_REPORT,
         TRAIN_POLL, GC)

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
# ops/eva_attention.py: the whole op (summaries, both kernels' calls), and
# inside it the chunk-summary pass alone
EVA_ATTENTION = "eva_attention"
EVA_PREP_KV = "eva_prep_kv"
SCOPES = (EMBED, BLOCK) + BLOCK_SCOPES + (MOE, LN_F, LM_HEAD_LOSS, OPTIMIZER,
                                          FLASH_ATTENTION, EVA_ATTENTION,
                                          EVA_PREP_KV)

# the two Mosaic kernels (`name=` of their pallas_call)
FLASH_FWD_KERNEL = "flash_attention_fwd"
FLASH_BWD_KERNEL = "flash_attention_bwd"
# EVA's aggregation (one softmax over a window's own keys and the summaries
# of every earlier window), forward and backward; the summary pass is XLA
EVA_AGG_FWD_KERNEL = "eva_agg_fwd"
EVA_AGG_BWD_KERNEL = "eva_agg_bwd"
KERNELS = (FLASH_FWD_KERNEL, FLASH_BWD_KERNEL, EVA_AGG_FWD_KERNEL,
           EVA_AGG_BWD_KERNEL)
# the tiling ops/attention.py chose for a kernel: one instant event per
# distinct decision, at trace time, in the task-event buffer
FLASH_TILING = "ops/flash_tiling"
FLASH_TILING_ARGS = ("kernel", "rows", "Sq", "Skv", "hd", "block_q", "block_k",
                     "vmem_estimate")
# the same for an EVA kernel (ops/eva_attention.py): Sq = Skv = the sequence
EVA_TILING = "ops/eva_tiling"
EVA_TILING_ARGS = FLASH_TILING_ARGS + ("window", "chunk")

# the block's residuals a `remat=True` checkpoint may keep, one name a tensor
# (`jax.ad_checkpoint.checkpoint_name`; an identity outside such a checkpoint):
# the three qkv einsums' outputs, the flash forward kernel's output and
# logsumexp (tagged in ops/attention._flash_fwd, where lse exists), x after
# the attention residual add, the MLP's hidden pre-activation. A block has
# the names of the tensors it computes: with the EVA mixer the aggregation
# kernel's output and log-normaliser and the chunk summaries stand where
# flash_o / flash_lse stand; a gated (SwiGLU) MLP has two hidden tensors.
RES_Q, RES_K, RES_V = "block_q", "block_k", "block_v"
RES_FLASH_O, RES_FLASH_LSE = "flash_o", "flash_lse"
RES_EVA_O, RES_EVA_LSE = "eva_o", "eva_lse"
RES_EVA_KT, RES_EVA_VT = "eva_k_summary", "eva_v_summary"
RES_MID = "block_mid"
RES_MLP_HIDDEN = "mlp_hidden"
RES_MLP_GATE, RES_MLP_UP = "mlp_gate", "mlp_up"
RESIDUALS = (RES_Q, RES_K, RES_V, RES_FLASH_O, RES_FLASH_LSE, RES_MID,
             RES_MLP_HIDDEN, RES_EVA_O, RES_EVA_LSE, RES_EVA_KT, RES_EVA_VT,
             RES_MLP_GATE, RES_MLP_UP)
# which of them models/gpt2.py chose to save, and the rows of the sequence
# the block's MLP and the LM head take at a time (the sequence: all at once):
# one instant event per distinct decision, at trace time, in the task-event
# buffer
REMAT_POLICY = "model/remat_policy"
REMAT_POLICY_ARGS = ("n_layer", "batch", "seq", "saved", "saved_bytes",
                     "budget_bytes", "bytes_limit", "mlp_rows", "head_rows")

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

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
# ops/mamba2.py: the Mamba-2 mixer (projections, conv, scan, gated norm) and,
# inside it, the chunked state-space scan alone
MAMBA = "mamba"
SSD_SCAN = "ssd_scan"
# ops/moe.latent_moe, under MOE: the routed experts (router, top-k, sort,
# gather, grouped products, combine) and, inside that, all of it but the
# grouped products; the latent projections around them; the shared expert
MOE_ROUTED = "moe_routed"
MOE_DISPATCH = "moe_dispatch"
MOE_LATENT = "moe_latent"
MOE_SHARED = "moe_shared"
# ops/moe._further_passes, inside MOE_ROUTED: the passes over the row buffer
# after the first — the `while` a batch enters only where its pairs fill more
# than one buffer — forward and backward: their lookups and masks, the loop's
# carried state, the float32 sums of the experts' weight gradients. The first
# pass stands outside it, so a step that runs nothing under this scope took
# the one-pass path in every expert layer (PR 51)
MOE_FURTHER_PASSES = "moe_further_passes"
# models/nemotron_h.py: the multi-token-prediction module, its layers and loss
MTP = "mtp"
# models/minicpm_sala.py: the lightning (decayed linear-attention) mixer —
# projections, QK-norm, RoPE, the output norm and gate — around SSD_SCAN, whose
# kernels run its recurrence; and the block-sparse mixer (ops/
# sparse_attention.py) with, inside it, the selection alone: compressed keys,
# their scores, pooling to blocks and `top_k` (XLA) — the two kernels are
# under SPARSE_ATTENTION and outside SPARSE_SELECT
LIGHTNING_ATTN = "lightning_attn"
SPARSE_ATTENTION = "sparse_attention"
SPARSE_SELECT = "sparse_select"
# models/lfm2_moe.py: the double-gated short convolution, the whole operator
# (in-projection, gates and conv, out-projection) and, inside it, what lies
# between the two products (ops/short_conv.py: the two gates and the
# depthwise causal conv — elementwise, the CONV_GATE_* kernels' work)
SHORT_CONV = "short_conv"
CONV_GATE = "conv_gate"
# models/deepseek_v2.py: multi-head latent attention's work between the norm
# and the flash kernel that is not the kernel — the q projection, the joint
# projection to the latent c and the one shared k_pe, the latent's norm, the
# up-projection to k_nope and v, RoPE on q_pe and k_pe, k put together from
# k_nope and the broadcast k_pe (after the kernel the out-projection is PROJ
# as anywhere); and an expert layer's balance loss (ops/moe.balance_loss:
# the per-row counts and mean probabilities, their product)
MLA_LATENT = "mla_latent"
MOE_AUX = "moe_aux"
# models/hyper_connections.py: all of a sublayer's hyper-connection work —
# the maps, the pre-mix Σ H_pre[i]·x[i] and the write-back H_res·x + H_post ⊗ y
# over the n-stream carry — and, inside it, the pass over x that makes the
# maps: since PR 58 the mix pair (ops/hyper_connections.py: the flattened
# stream's RMS and the Φ product from one read of x, with the pre-mix riding
# on the same read; backward the one kernel that writes d x) and, XLA's, the
# sigmoids and the Sinkhorn rounds on the logits' planes
MHC = "mhc"
MHC_MAPS = "mhc_maps"
# models/qwen3_next.py: the Gated DeltaNet mixer — the fused q, k, v, z
# projection and the b, a one, the causal conv with its SiLU, the L2 norms,
# the gates, the scan, the gated per-head norm and the out-projection — and,
# inside it, the scan alone (ops/gated_delta.gated_delta_scan: the kernel
# pair and what XLA prepares for it — the cumulative gates, padding, the
# rows' layout); and, inside the gated attention layer's `attn`, the sigmoid
# output gate's product with the kernel's output
DELTA_MIXER = "delta_mixer"
GATED_DELTA = "gated_delta"
GATED_ATTN_GATE = "gated_attn_gate"
# models/llama.py's looped (Ouro) configs: the second norm on each sublayer's
# OUTPUT, before its residual add (the attention's inside `proj`, the MLP's
# inside its row chunk beside `mlp`); and everything of the objective that is
# not the head — the gate's product with each pass's state, the exit
# distribution over the passes, its entropy, the step's mean of each. The
# loop-end norm is LN_F: it stands after the last layer of EVERY pass
LN1_POST, LN2_POST = "ln1_post", "ln2_post"
EXIT_GATE = "exit_gate"
# models/afmoe.py: inside `attn`, the attention operator of a WINDOW layer
# (the flash pair under a causal window, its output gate) and of a FULL one —
# both kinds run kernels of one name, and a trace tells their time apart by
# these
ATTN_WINDOW, ATTN_FULL = "attn_window", "attn_full"
SCOPES = (EMBED, BLOCK) + BLOCK_SCOPES + (MOE, LN_F, LM_HEAD_LOSS, OPTIMIZER,
                                          FLASH_ATTENTION, EVA_ATTENTION,
                                          EVA_PREP_KV, MAMBA, SSD_SCAN,
                                          MOE_ROUTED, MOE_DISPATCH, MOE_LATENT,
                                          MOE_SHARED, MTP, LIGHTNING_ATTN,
                                          SPARSE_ATTENTION, SPARSE_SELECT,
                                          SHORT_CONV, CONV_GATE,
                                          MOE_FURTHER_PASSES, MLA_LATENT,
                                          MOE_AUX, MHC, MHC_MAPS,
                                          DELTA_MIXER, GATED_DELTA,
                                          GATED_ATTN_GATE, LN1_POST, LN2_POST,
                                          EXIT_GATE, ATTN_WINDOW, ATTN_FULL)

# the two Mosaic kernels (`name=` of their pallas_call)
FLASH_FWD_KERNEL = "flash_attention_fwd"
FLASH_BWD_KERNEL = "flash_attention_bwd"
# EVA's aggregation (one softmax over a window's own keys and the summaries
# of every earlier window), forward and backward; the summary pass is XLA
EVA_AGG_FWD_KERNEL = "eva_agg_fwd"
EVA_AGG_BWD_KERNEL = "eva_agg_bwd"
# the held experts' grouped products (ops/moe._pass_rows), whoever makes
# them. Since PR 60 the program's own kernels (ops/grouped_matmul.py:
# `grouped_gmm`, `grouped_gmm_t`, `grouped_tgmm`) carry this name as a scope
# around each call — and NOT `moe_routed`: the benchmark's reader adds this
# kernel's time to that scope's. Where the rule leaves a call to
# `lax.ragged_dot` it is the TPU compiler's own grouped-matmul kernel, whose
# instructions are named `ragged-dot-…` and carry NO op_name — the scopes
# they were written under are gone, so a trace's reader knows them by this
# name alone. (The name is the compiler's; the readers were written to it.)
RAGGED_DOT_KERNEL = "ragged-dot"
# the state-space scan (ops/mamba2.ssd_scan): a chunk's quadratic form, its
# state update and the carried state's part of y, a (row, head tile) at a time
# along the row's chunks — and the same tiles' gradients, the chunks reversed
SSD_CHUNK_FWD_KERNEL = "ssd_chunk_fwd"
SSD_CHUNK_BWD_KERNEL = "ssd_chunk_bwd"
# attention over the key blocks each query was given (ops/
# sparse_attention.py): a tile is the 16 query heads of one key-value head for
# a run of tokens against a run of key blocks, masked by who chose what. The
# backward is two calls, each with a name of its own (a trace's reader knows
# a kernel by the name listed here, so a third pass must be listed too)
SPARSE_ATTN_FWD_KERNEL = "sparse_attn_fwd"
SPARSE_ATTN_BWD_DQ_KERNEL = "sparse_attn_bwd_dq"
SPARSE_ATTN_BWD_DKV_KERNEL = "sparse_attn_bwd_dkv"
# the gates and the short conv between the LFM2 conv operator's two products
# (ops/short_conv.py): a run of a row's tokens at the whole width — BCx in
# and y out; BCx and d y in, d BCx and d w's partial sums out
CONV_GATE_FWD_KERNEL = "conv_gate_fwd"
CONV_GATE_BWD_KERNEL = "conv_gate_bwd"
# the passes over a hyper-connection's n-stream carry (ops/
# hyper_connections.py), a tile of tokens at the carry's whole width: the mix
# (x in once: the maps' normalised logits and the pre-mix u out; backward the
# ONE kernel that sums and writes d x, with d Φ's sum) and the write-back
# (x, y and the maps in, x' out; backward d y, the maps' cotangents and the
# carry's part H_resᵀ · d x')
MHC_MIX_FWD_KERNEL = "mhc_mix_fwd"
MHC_MIX_BWD_KERNEL = "mhc_mix_bwd"
MHC_WRITE_FWD_KERNEL = "mhc_write_fwd"
MHC_WRITE_BWD_KERNEL = "mhc_write_bwd"
# the gated delta rule (ops/gated_delta.py): a chunk's masked products after
# its unit-lower-triangular solve, the state's correction and the carried
# state's part of o, a (row, tile of one key head's value heads) at a time
# along the row's chunks — and the same tiles' gradients, the chunks reversed.
# Both read the solve's result, (I + A)⁻¹ of every chunk, which a third
# kernel makes once with no sequential axis. ITS NAME BEGINS WITH THE
# FORWARD'S and stands after it in KERNELS: a reader that finds a kernel by
# the first of KERNELS inside an instruction's name counts the solve's time
# as the forward's, where it was before it had a kernel of its own
GATED_DELTA_FWD_KERNEL = "gated_delta_fwd"
GATED_DELTA_SOLVE_KERNEL = "gated_delta_fwd_solve"
GATED_DELTA_BWD_KERNEL = "gated_delta_bwd"
# the Gated DeltaNet mixer's elementwise work on either side of that rule
# (ops/delta_pointwise.py), a run of a row's tokens at a tensor's whole width,
# a head's channels whole lane tiles: conv + SiLU + per-head L2 norm of one
# projection's output (backward: d x and d w's partial sums), and the
# per-head RMS norm of o times gain times silu(z) (backward: d o, d z and
# d gain's partial sums). They run under DELTA_MIXER and outside GATED_DELTA;
# no name holds an older kernel's, and none of those holds one of these
DELTA_CONV_NORM_FWD_KERNEL = "delta_conv_norm_fwd"
DELTA_CONV_NORM_BWD_KERNEL = "delta_conv_norm_bwd"
DELTA_GATE_NORM_FWD_KERNEL = "delta_gate_norm_fwd"
DELTA_GATE_NORM_BWD_KERNEL = "delta_gate_norm_bwd"
# the gated attention operator's elementwise work on either side of its flash
# pair (ops/attention_pointwise.py), a run of a row's tokens of a few heads:
# QK-norm (+ RoPE on a window layer) of a q or k projection's output, read
# [B, S, H · hd] where the product writes it and written [B, H, S, hd] for
# the flash pair (backward: d x and d gain's partial sums), and o · sigmoid(
# gate) the other way round (backward: d o and d gate). The first pair runs
# under QKV, the second under GATED_ATTN_GATE; no name holds an older
# kernel's, and none of those holds one of these
HEAD_NORM_ROPE_FWD_KERNEL = "head_norm_rope_fwd"
HEAD_NORM_ROPE_BWD_KERNEL = "head_norm_rope_bwd"
ATTN_GATE_FWD_KERNEL = "attn_gate_fwd"
ATTN_GATE_BWD_KERNEL = "attn_gate_bwd"
KERNELS = (FLASH_FWD_KERNEL, FLASH_BWD_KERNEL, EVA_AGG_FWD_KERNEL,
           EVA_AGG_BWD_KERNEL, RAGGED_DOT_KERNEL, SSD_CHUNK_FWD_KERNEL,
           SSD_CHUNK_BWD_KERNEL, SPARSE_ATTN_FWD_KERNEL,
           SPARSE_ATTN_BWD_DQ_KERNEL, SPARSE_ATTN_BWD_DKV_KERNEL,
           CONV_GATE_FWD_KERNEL, CONV_GATE_BWD_KERNEL, MHC_MIX_FWD_KERNEL,
           MHC_MIX_BWD_KERNEL, MHC_WRITE_FWD_KERNEL, MHC_WRITE_BWD_KERNEL,
           GATED_DELTA_FWD_KERNEL, GATED_DELTA_SOLVE_KERNEL,
           GATED_DELTA_BWD_KERNEL, DELTA_CONV_NORM_FWD_KERNEL,
           DELTA_CONV_NORM_BWD_KERNEL, DELTA_GATE_NORM_FWD_KERNEL,
           DELTA_GATE_NORM_BWD_KERNEL, HEAD_NORM_ROPE_FWD_KERNEL,
           HEAD_NORM_ROPE_BWD_KERNEL, ATTN_GATE_FWD_KERNEL,
           ATTN_GATE_BWD_KERNEL)
# the tiling ops/attention.py chose for a kernel: one instant event per
# distinct decision, at trace time, in the task-event buffer
FLASH_TILING = "ops/flash_tiling"
_TILE_ARGS = ("kernel", "rows", "Sq", "Skv", "hd", "block_q", "block_k",
              "vmem_estimate")
# ... and which kernel pair it was traced for: "s_minor" ([rows, hd, S]
# operands, where a width is not whole lane tiles) or "hd_minor"; `hd` is q's
# and k's width and, since PR 55, `hd_v` v's and o's (latent attention's 192
# and 128; equal anywhere else); and, since PR 66, the causal `window` the
# call was given (0: none) with the (q, kv) tile pairs it visits beside those
# the causal walk alone would (equal without a window): how much of the
# triangle the band skipped
FLASH_TILING_ARGS = _TILE_ARGS + ("layout", "hd_v", "window", "tiles_visited",
                                  "tiles_causal")
# the same for an EVA kernel (ops/eva_attention.py): Sq = Skv = the sequence
EVA_TILING = "ops/eva_tiling"
EVA_TILING_ARGS = _TILE_ARGS + ("window", "chunk")
# the same for a scan kernel (ops/mamba2.py): the batch rows, the (padded)
# sequence, the chunk, the heads that share a group's B and C, head width and
# state, and the heads one grid step takes
SSD_TILING = "ops/ssd_tiling"
SSD_TILING_ARGS = ("kernel", "rows", "S", "Q", "group_heads", "P", "N",
                   "head_tile", "vmem_estimate")
# the same for a delta-rule kernel (ops/gated_delta.py): the batch rows, the
# (padded) sequence, the chunk, the key heads and the value heads each serves,
# the two head widths, the value heads of one key head a grid step stacks and
# the key heads (each such a stack) it takes
DELTA_TILING = "ops/delta_tiling"
DELTA_TILING_ARGS = ("kernel", "rows", "S", "C", "key_heads",
                     "value_heads_per_key", "dk", "dv", "head_tile",
                     "key_tile", "vmem_estimate")
# ... and, under the same event, for a kernel of the mixer's elementwise work
# around the rule (ops/delta_pointwise.py: "conv_norm_fwd" / "_bwd",
# "gate_norm_fwd" / "_bwd"): the batch rows, the (padded) sequence and width,
# the heads a norm sums over apart (0: no norm), the tokens a grid step takes
# and the lanes its body works on at a time
DELTA_POINTWISE_TILING_ARGS = ("kernel", "rows", "S", "channels", "heads",
                               "token_tile", "channel_tile", "vmem_estimate")
# the same for a block-sparse attention kernel ("fwd", "bwd_dq", "bwd_dkv"):
# the (batch x key-value head) rows, the sequence, the query heads that share
# a key-value head (the rows of a tile's products, with `block_q` tokens),
# the head width, the key block the selection is made in and how many a
# query is given, and the tile: tokens of queries x keys
SPARSE_TILING = "ops/sparse_tiling"
SPARSE_TILING_ARGS = ("kernel", "rows", "S", "group_heads", "hd", "block",
                      "blocks_per_query", "block_q", "block_k",
                      "vmem_estimate")
# the same for a hyper-connection kernel ("mix_fwd", "mix_bwd", "write_fwd",
# "write_bwd"; ops/hyper_connections.py): the tokens a device holds, the
# streams and a stream's width, the tokens a grid step takes at the carry's
# whole width
MHC_TILING = "ops/mhc_tiling"
MHC_TILING_ARGS = ("kernel", "tokens", "n", "C", "token_tile",
                   "vmem_estimate")
# the same for a held experts' grouped product (ops/grouped_matmul.py; `form`
# is "gmm" x·W, "gmm_t" d·Wᵀ or "tgmm" xᵀ·d): the rows of the buffer, the
# held experts, an expert's matrix [K, N], its bytes an element and the
# devices of the step's mesh; which implementation took the call ("pallas":
# the program's kernel, "compiler": `lax.ragged_dot`'s) and the rows of one
# visit
GROUPED_TILING = "ops/grouped_tiling"
GROUPED_TILING_ARGS = ("form", "rows", "held", "K", "N", "dtype_bytes",
                       "devices", "impl", "row_tile", "vmem_estimate")
# what a block-sparse mixer's selection is, once a distinct shape (models/
# minicpm_sala.py): rows and sequence, the key blocks there are, how many a
# query is given of them, how many of those are forced (the window's and the
# initial ones), the row length up to which attention stays dense, which of
# the two this trace runs, and the share of a row's visible keys that are
# kept (1.0 dense)
SPARSE_SELECTION = "model/sparse_selection"
SPARSE_SELECTION_ARGS = ("rows", "S", "blocks", "top_k", "window_blocks",
                         "init_blocks", "dense_len", "mode", "kept_share")

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
# a Mamba-2 layer's: the three projections' outputs (z, xBC before the conv,
# dt), the state each chunk of the scan starts from, the scan's output. An
# expert layer's: the latent input of the routed experts, the shared expert's
# hidden pre-activation; and what its routing decided (PR 42) — the router's
# sigmoid scores, the `top_k`'s last value and index a token (the chosen set
# is one elementwise pass from them, ops/moe._chosen), the sorted keys of the
# pairs on held experts and, since PR 51, the gates the same sort laid beside
# them (ops/moe.held_pairs).
RES_MAMBA_Z, RES_MAMBA_XBC, RES_MAMBA_DT = "mamba_z", "mamba_xbc", "mamba_dt"
RES_SSD_STATES, RES_SSD_Y = "ssd_states", "ssd_y"
RES_MOE_LATENT, RES_MOE_SHARED_HIDDEN = "moe_latent_in", "moe_shared_hidden"
RES_MOE_SCORES = "moe_scores"
RES_MOE_KTH, RES_MOE_LAST = "moe_kth", "moe_kth_index"
RES_MOE_PAIR_KEY = "moe_pair_key"
RES_MOE_PAIR_GATE = "moe_pair_gate"
# a MiniCPM-SALA layer's (models/minicpm_sala.py). Both mixers name q, k, v
# (RES_Q, RES_K, RES_V: after the QK-norm and, in the lightning one, RoPE) and
# their output gate's pre-activation; the lightning one the scan's states and
# its output before the output norm (RES_SSD_STATES, and its own name for y:
# [B, S, H, P] in the compute dtype, not the Mamba mixer's merged one); the
# sparse one the chosen block ids — small, and a scoring pass and a `top_k`
# to make again — and the kernel's output and log-sum-exp. The SwiGLU half
# names RES_MID, RES_MLP_GATE and RES_MLP_UP as the llama block does.
RES_SALA_GATE = "sala_gate"
RES_LIGHTNING_Y = "lightning_y"
RES_SPARSE_IDS = "sparse_block_ids"
RES_SPARSE_O, RES_SPARSE_LSE = "sparse_o", "sparse_lse"
# an LFM2 layer's (models/lfm2_moe.py). The short-convolution operator names
# its in-projection's output, [B, S, 3·D]: the two gates and the conv's input
# in one tensor, the operator's one product to make again. Its attention
# names q, k (after the QK-norm and RoPE) and v as the others do, both
# feed-forward halves RES_MID, the dense one RES_MLP_GATE and RES_MLP_UP, the
# expert layer what its routing decided (RES_MOE_SCORES … RES_MOE_PAIR_KEY).
# The held experts' hidden tensors have no name: they are a pass's, inside
# the dispatch's written-out backward (ops/moe._run_passes)
RES_CONV_BCX = "conv_bcx"
# a DeepSeek-V2 layer's (models/deepseek_v2.py). Latent attention names its
# normed latent c and the rotated shared k_pe, [B, S, 512] and [B, 64, S]: 576
# numbers a token that stand for the 16 × (192 + 128) of k and v (one
# up-projection and a concatenation away), beside q, k and v themselves
# (RES_Q, RES_K, RES_V: k after the concatenation) and the flash kernel's two;
# both feed-forward halves RES_MID, the dense one RES_MLP_GATE / RES_MLP_UP,
# the expert layer the routing's names (RES_MOE_SCORES holds the softmax's
# probabilities there) and its shared expert's two hidden tensors
RES_MLA_C, RES_MLA_KPE = "mla_latent_c", "mla_k_pe"
RES_MOE_SHARED_GATE, RES_MOE_SHARED_UP = "moe_shared_gate", "moe_shared_up"
# a Qwen3-Next layer's (models/qwen3_next.py). The Gated DeltaNet mixer names
# its fused projection's four parts (q, k, v before the conv, and z: the
# mixer's one large weight, a product a part) and the b, a one's, the solve's
# (I + A)⁻¹ of every chunk (the value heads' [C, C] blocks in the compute
# dtype: the one residual of the scan that spares a kernel call alone), the
# state each chunk of the scan starts from and the scan's output (together:
# the forward call); the gated attention layer q, k (after the QK-norm
# and the partial rotation) and v as the others do, the flash kernel's two,
# and its output gate's pre-activation; both halves RES_MID and the expert
# half the routing's names and the shared expert's two hidden tensors
RES_DELTA_PARTS = ("delta_q", "delta_k", "delta_v", "delta_z")
RES_DELTA_BA = "delta_ba"
RES_DELTA_STATES, RES_DELTA_O = "delta_states", "delta_o"
RES_DELTA_X = "delta_x"
RES_ATTN_GATE = "attn_gate"
RESIDUALS = (RES_Q, RES_K, RES_V, RES_FLASH_O, RES_FLASH_LSE, RES_MID,
             RES_MLP_HIDDEN, RES_EVA_O, RES_EVA_LSE, RES_EVA_KT, RES_EVA_VT,
             RES_MLP_GATE, RES_MLP_UP, RES_MAMBA_Z, RES_MAMBA_XBC, RES_MAMBA_DT,
             RES_SSD_STATES, RES_SSD_Y, RES_MOE_LATENT, RES_MOE_SHARED_HIDDEN,
             RES_MOE_SCORES, RES_MOE_KTH, RES_MOE_LAST, RES_MOE_PAIR_KEY,
             RES_SALA_GATE, RES_LIGHTNING_Y, RES_SPARSE_IDS, RES_SPARSE_O,
             RES_SPARSE_LSE, RES_CONV_BCX, RES_MOE_PAIR_GATE, RES_MLA_C,
             RES_MLA_KPE, RES_MOE_SHARED_GATE, RES_MOE_SHARED_UP,
             *RES_DELTA_PARTS, RES_DELTA_BA, RES_DELTA_STATES, RES_DELTA_O,
             RES_ATTN_GATE, RES_DELTA_X)
# which of them models/blocks.py chose to save, the rows of the sequence
# the block's MLP and the LM head take at a time (the sequence: all at once),
# and the phase of the backward whose working set the budget was left by
# (blocks.backward_phases: "head", or a run of the layers as model/layer_pattern
# names it) with that set's bytes: one instant event per distinct decision, at
# trace time, in the task-event buffer
REMAT_POLICY = "model/remat_policy"
REMAT_POLICY_ARGS = ("n_layer", "batch", "seq", "saved", "saved_bytes",
                     "budget_bytes", "bytes_limit", "mlp_rows", "head_rows",
                     "phase", "phase_bytes")
# ... and, of a model that runs its layers several times on one set of
# weights (blocks.run_repeated), the passes and the block applications a kept
# residual is copied for (n_layer stays the LAYERS: the weight gradients'
# slices); a model of one pass says what it always said
REMAT_POLICY_LOOP_ARGS = ("passes", "applications")
# a head that takes the sequence in chunks (ops/cross_entropy.
# chunked_head_xent): the batch rows and the positions a chunk holds, the
# chunks, the head's columns (all heads') and heads, whether this trace makes
# each chunk's gradient beside its loss (under differentiation) or only the
# loss, the bytes it then keeps for the backward (d x, the float32
# d lm_head), and the bytes of that float32 d lm_head the step moves — the
# chunk scan's carry, read and written whole by every chunk: chunks x 2 x
# d_model x columns x 4, what parts.head_chunk_rows gives a chunk tokens
# enough to hide (0 where the trace makes no gradient); one instant event per
# distinct decision, at trace time
HEAD_LOSS = "model/head_loss"
HEAD_LOSS_ARGS = ("batch", "rows", "chunks", "columns", "heads",
                  "grad_in_forward", "residual_bytes", "carry_bytes_a_step")
# a model whose layers are of more than one kind (blocks.run_pattern): the
# pattern, how often each kind is applied and which runs of it are one scan;
# one instant event per distinct pattern, at trace time
LAYER_PATTERN = "model/layer_pattern"
LAYER_PATTERN_ARGS = ("pattern", "applications", "groups")
# a model whose layers run several times on ONE set of weights
# (blocks.run_repeated): the passes, the layers of one pass, the block
# applications a step makes (passes x layers), the float32 bytes of one
# stack of the layers' weight gradients on a chip (the backward holds the
# running sum and at most one pass's stack beside it) and how the heads are
# called ("one call over passes x batch rows": the model says); one instant
# event per distinct decision, at trace time. A model/layer_pattern event of
# the same trace says the pattern of ONE pass and these `passes`
LOOP = "model/loop"
LOOP_ARGS = ("passes", "layers", "applications", "grad_stack_bytes", "heads")
# a model whose residual path is a hyper-connection (models/
# hyper_connections.py): the streams, the Sinkhorn rounds, the stream's dtype
# and the bytes a token's carry takes; one instant event per distinct
# decision, at trace time
HYPER_CONNECTION = "model/hyper_connection"
HYPER_CONNECTION_ARGS = ("streams", "rounds", "stream_dtype",
                         "carry_bytes_per_token")
# what the held experts of each expert layer are sent by one batch, its
# selection bias balanced on it (nemotron_h.balance_router_bias, from the
# first batch at set-up): pairs landed here,
# the largest and the mean over the held experts, tokens with no held expert,
# the row buffer, the passes over it the pairs fill, how full those passes are
# (pairs ÷ (passes · rows), PR 36), and the pairs in no pass (none: the passes
# cover the worst case)
EXPERT_LOAD = "model/expert_load"
EXPERT_LOAD_ARGS = ("layer", "tokens", "pairs", "max_per_expert",
                    "mean_per_expert", "tokens_without_held_expert",
                    "buffer_rows", "buffer_passes", "buffer_fill",
                    "pairs_dropped")
# ... and what EVERY step's batch sends them, out of the compiled step itself
# (ops/moe.routed_experts returns it beside its result; PR 52): the passes
# over the row buffer the step's pairs filled (EXPERT_LOAD's `buffer_passes`,
# a value of the step: the trip count of ops/moe._run_passes), the pairs
# landed and the fullest held expert's — what the dispatch has in hand, no
# reduction over the tokens of its own
STEP_EXPERT_LOAD_ARGS = ("passes", "pairs", "max_per_expert")
# ... and, of a layer whose router is balanced by an auxiliary loss (PR 55:
# ops/moe.balance_loss, the DeepSeek-V2 family's), that loss's value in the
# step, BEFORE its coefficient: a float32 whose bits ride in the int32
# counters (models/blocks.StepCounters.float_fields)
STEP_BALANCE_LOSS = "balance_loss"
# what a looped model with an exit gate (models/llama.py, `exit_gate`) says of
# every step: ONE row, the step's mean over its valid tokens of the exit
# distribution's mass on each pass (`exit_p1` … `exit_p<T>`, summing to 1) and
# of its entropy — all float32 bits in the int32 counters, constants to AD
EXIT_DISTRIBUTION_KIND = "exit_distribution"
STEP_EXIT_PASS = "exit_p"            # + the pass, from 1
STEP_EXIT_ENTROPY = "exit_entropy"
EXIT_DISTRIBUTION_STATIC_ARGS = ("passes",)

# host spans: `ray_tpu:<component>/<name>` on the profiler's clock,
# `<component>/<name>` with that component in the task-event buffer
SPAN_PREFIX = "ray_tpu:"
DATA_GET_BLOCK = "data/get_block"
DATA_ASSEMBLE = "data/assemble"
DATA_DEVICE_PUT = "data/device_put"
TRAIN_REPORT = "train/report"
GC = "gc"
BG = "bg"                        # `bg/<loop>`: one span a tick of a periodic loop
# the call of the compiled step (train/train_step.make_train_step's
# callable, around the jitted call: the enqueue, or the wait on a full
# queue), args `step` = the callable's own count of calls
TRAIN_STEP = "train/step"
SPANS = (DATA_GET_BLOCK, DATA_ASSEMBLE, DATA_DEVICE_PUT, TRAIN_REPORT, GC,
         TRAIN_STEP)
# the ONE event a step: what the compiled step said it did (PR 52). A model
# that offers counters (`step_counters(cfg)`: the expert families') returns
# them from its step as one int32 array, `metrics["counters"]`; the step's
# callable hands the array to tracing/step_counters.py, which records it —
# when the device has made it, never waiting on the loop's path — as an
# instant in the task-event buffer: `step` the TRAIN_STEP span's, `kind` what
# the rows are ("expert_load": STEP_EXPERT_LOAD_ARGS), `t_dispatch` the
# `time.time()` of the step's call (the event's own `ts` is when it was
# recorded), `layers` the published ids of the layers that report, then one
# list a field with an entry a layer, then the kind's static args (the row
# buffer one pass takes, the experts held)
TRAIN_STEP_COUNTERS = "train/step_counters"
TRAIN_STEP_COUNTERS_ARGS = ("step", "kind", "t_dispatch", "layers")
EXPERT_LOAD_KIND = "expert_load"
EXPERT_LOAD_STATIC_ARGS = ("buffer_rows", "held")

# ---- set-up and teardown (PR 35): spans recorded once an attempt, a split,
# a process or a session — never a step. All on `time.time()`, in the
# task-event buffer -> `ray_tpu.timeline()` and `<session_dir>/timeline.json`;
# each name with the tuple of its args, in order.
# One trace per attempt of `DataParallelTrainer.fit()`: the driver's spans
# below, the tasks submitted under them and what the worker records in its
# loop share the attempt's `trace_id`. TRAIN_FIT holds the others.
TRAIN_FIT = "train/fit"
TRAIN_FIT_ARGS = ("name", "attempt", "num_workers", "tpus_per_worker")
# the `WorkerGroup(...)` constructor, placement-group wait included
TRAIN_WORKER_GROUP_START = "train/worker_group_start"
TRAIN_WORKER_GROUP_START_ARGS = ("num_workers",)
# `WorkerGroup.rendezvous`, only where it does something (> 1 worker); each
# rank's `jax.distributed.initialize` inside it, on the worker
TRAIN_RENDEZVOUS = "train/rendezvous"
TRAIN_RENDEZVOUS_ARGS = ("num_workers",)
TRAIN_JAX_DISTRIBUTED_INIT = "train/jax_distributed_init"
TRAIN_JAX_DISTRIBUTED_INIT_ARGS = ("rank", "num_processes")
TRAIN_SHARD_DATASETS = "train/shard_datasets"
TRAIN_SHARD_DATASETS_ARGS = ("datasets",)
# first `start_training` submitted -> every rank's call returned: what is left
# of the worker's start once nothing else hides it
TRAIN_START_TRAINING = "train/start_training"
TRAIN_START_TRAINING_ARGS = ("num_workers",)
TRAIN_DRIVE = "train/drive"
TRAIN_DRIVE_ARGS = ("reports", "error")
# `WorkerGroup.shutdown`: the `ray_tpu.kill` calls it made, what they raised
# (swallowed as before; "<Type>: <message>" cut to 200 characters, a list) and
# the workers whose process the raylet had confirmed gone when the span
# closed — the kills whose GCS_KILL_ACTOR outcome was "reaped". Fewer gone
# than killed: `fit()` returned while a process still held its chips
TRAIN_GROUP_SHUTDOWN = "train/group_shutdown"
TRAIN_GROUP_SHUTDOWN_ARGS = ("num_workers", "killed", "kill_errors",
                             "gone_at_return")
# instants on the loop's own thread: immediately before the user's function
# is called, and when it has returned or raised
TRAIN_LOOP_ENTERED = "train/loop_entered"
TRAIN_LOOP_ENTERED_ARGS = ("rank", "pid")
TRAIN_LOOP_DONE = "train/loop_done"
TRAIN_LOOP_DONE_ARGS = ("rank", "error")
# every backend compile of a train worker's process, a load from the
# persistent cache included (`jax.monitoring`'s backend_compile_duration):
# `seconds` is JAX's own number, `cache` "hit" / "miss" where the cache's
# events said so, else None. Also on the profiler's clock.
TRAIN_COMPILE = "train/compile"
TRAIN_COMPILE_ARGS = ("fun_name", "seconds", "cache")
# the first initialisation of a JAX backend in a train worker's process, on
# the thread that asked for it (the loop's, at the user's own first
# `jax.devices()`): JAX's `xla_bridge._init_backend` from its first DEBUG
# line to its last, observed (`tracing/backend_init.py`), never called
TRAIN_BACKEND_INIT = "train/backend_init"
TRAIN_BACKEND_INIT_ARGS = ("rank", "platform", "devices", "seconds")

# `Dataset.split`: DATA_SPLIT holds the three others
DATA_SPLIT = "data/split"
DATA_SPLIT_ARGS = ("n", "blocks", "rows")
DATA_MATERIALIZE = "data/materialize"
DATA_MATERIALIZE_ARGS = ("stages", "blocks_in", "blocks_out")
DATA_COUNT_ROWS = "data/count_rows"
DATA_COUNT_ROWS_ARGS = ("blocks",)
DATA_SLICE = "data/slice"
DATA_SLICE_ARGS = ("tasks",)
# The tasks these submit show in the record as slices RUNNING -> EXECUTED and
# as `<task>:<STATE>` instants (`tracing/events.py`'s lifecycle states). A
# task submitted beside its producer waits AT ITS OWNER, holding no worker
# (PR 40): its instants read SUBMITTED, this state, then DISPATCHED once the
# objects it takes by reference exist, and its slice is its own work
TASK_PENDING_ARGS_AVAIL = "PENDING_ARGS_AVAIL"

# a worker process, in the raylet: `WorkerPool.start_worker` -> that token's
# `on_register` (interpreter start, importing the package, connecting), and
# kill -> the process gone (`WorkerPool.reap`: a process that held chips
# frees them only then). `kind` is "actor" or "pooled".
RAYLET_WORKER_START = "raylet/worker_start"
RAYLET_WORKER_START_ARGS = ("pid", "startup_token", "platform", "kind")
RAYLET_WORKER_REAP = "raylet/worker_reap"
RAYLET_WORKER_REAP_ARGS = ("pid", "platform", "seconds", "timed_out", "cause")
# ... `cause`: who killed the process that is waited for — a `kill_actor`
# (`handle_kill_actor_worker`), the raylet's own SIGTERM (`WorkerPool.
# shutdown`: the driver's `shutdown()`), or "exit": it had been killed
# before — its owner's exit (a pooled worker still leased to a driver that
# disconnected), a chaos plan — and is only collected here
REAP_KILL_ACTOR, REAP_SIGTERM, REAP_EXIT = "kill_actor", "sigterm", "exit"

# a worker's first load of a function id — the GCS's blob fetched and
# unpickled, with every import that pulls in (`TrainWorker`: `ray_tpu.train`
# and JAX): `WorkerAgent._init_actor`'s, always (`kind` "actor"), and a plain
# task's where it lasted PROFILE_MIN_DUR_S (`kind` "task"); under the
# creating call's task and trace. `name` is the class's or function's
# qualified name, `bytes` the blob's, `modules_imported` how many entries
# `sys.modules` grew by
WORKER_LOAD_CLASS = "worker/load_class"
WORKER_LOAD_CLASS_ARGS = ("fn_id", "name", "kind", "bytes", "modules_imported",
                          "seconds")

# one `GcsServer.handle_kill_actor` call: what the GCS knew (the actor's
# `state` in its table, `node_alive`, `had_address`), whether it asked the actor's raylet (`forwarded`) and what
# came of it — "reaped": the raylet replied, the process is gone;
# "not_found": it replied that it holds no such worker; "rpc_error" /
# "connection_lost": the call raised (`error`: type and message, cut to 200
# characters; swallowed as before); "not_forwarded"; "unknown_actor"
GCS_KILL_ACTOR = "gcs/kill_actor"
GCS_KILL_ACTOR_ARGS = ("actor_id", "class_name", "no_restart", "state",
                       "node_alive", "had_address", "forwarded", "outcome",
                       "seconds", "error")
KILL_REAPED = "reaped"

# the driver's `init()` and `shutdown()` (ClusterBackend), and inside the
# latter one span a daemon process waited on
DRIVER_INIT = "driver/init"
DRIVER_INIT_ARGS = ("session", "started_cluster")
DRIVER_SHUTDOWN = "driver/shutdown"
DRIVER_SHUTDOWN_ARGS = ("session",)
DRIVER_WAIT_PROCESS = "driver/wait_process"
DRIVER_WAIT_PROCESS_ARGS = ("name", "pid", "seconds", "killed")
# the record's account of itself, the LAST event `shutdown()` appends: a row a
# source (a process's buffer: `source`, its cumulative `recorded`, `delivered`
# — acknowledged by the GCS — and `dropped` since its flush loop started, as
# the GCS last heard them, the driver's own as they stand; `recovered` = what
# whoever closed the record took from an in-flight batch, an unflushed buffer
# or a WAL file; `lost` = max(dropped, recorded - delivered - recovered)),
# the aggregator's `evicted_tasks`, `truncated_events` and `setup_evicted`,
# and of the driver's own buffer when its flush loop was stopped: the events
# `in_flight` (popped, not yet acknowledged), the set-up spans still
# `unflushed_setup` (names), the seconds since its last drain (`flush_age_s`)
# and from the stop to the core worker's loop gone (`window_s`) — a flush
# would have fired in that window, and its batch been in neither place,
# where flush_age_s + window_s reaches the flush period. A record without
# this event lost its tail or comes from a tree that wrote none
DRIVER_RECORD_SUMMARY = "driver/record_summary"
DRIVER_RECORD_SUMMARY_ARGS = ("sources", "evicted_tasks", "truncated_events",
                              "setup_evicted", "in_flight", "unflushed_setup",
                              "flush_age_s", "window_s")
RECORD_SOURCE_ARGS = ("source", "recorded", "delivered", "recovered",
                      "dropped", "lost")

SETUP_SPANS = {
    TRAIN_FIT: TRAIN_FIT_ARGS,
    TRAIN_WORKER_GROUP_START: TRAIN_WORKER_GROUP_START_ARGS,
    TRAIN_RENDEZVOUS: TRAIN_RENDEZVOUS_ARGS,
    TRAIN_JAX_DISTRIBUTED_INIT: TRAIN_JAX_DISTRIBUTED_INIT_ARGS,
    TRAIN_SHARD_DATASETS: TRAIN_SHARD_DATASETS_ARGS,
    TRAIN_START_TRAINING: TRAIN_START_TRAINING_ARGS,
    TRAIN_DRIVE: TRAIN_DRIVE_ARGS,
    TRAIN_GROUP_SHUTDOWN: TRAIN_GROUP_SHUTDOWN_ARGS,
    TRAIN_LOOP_ENTERED: TRAIN_LOOP_ENTERED_ARGS,
    TRAIN_LOOP_DONE: TRAIN_LOOP_DONE_ARGS,
    TRAIN_COMPILE: TRAIN_COMPILE_ARGS,
    TRAIN_BACKEND_INIT: TRAIN_BACKEND_INIT_ARGS,
    DATA_SPLIT: DATA_SPLIT_ARGS,
    DATA_MATERIALIZE: DATA_MATERIALIZE_ARGS,
    DATA_COUNT_ROWS: DATA_COUNT_ROWS_ARGS,
    DATA_SLICE: DATA_SLICE_ARGS,
    RAYLET_WORKER_START: RAYLET_WORKER_START_ARGS,
    RAYLET_WORKER_REAP: RAYLET_WORKER_REAP_ARGS,
    DRIVER_INIT: DRIVER_INIT_ARGS,
    DRIVER_SHUTDOWN: DRIVER_SHUTDOWN_ARGS,
    DRIVER_WAIT_PROCESS: DRIVER_WAIT_PROCESS_ARGS,
    DRIVER_RECORD_SUMMARY: DRIVER_RECORD_SUMMARY_ARGS,
    WORKER_LOAD_CLASS: WORKER_LOAD_CLASS_ARGS,
    GCS_KILL_ACTOR: GCS_KILL_ACTOR_ARGS,
}

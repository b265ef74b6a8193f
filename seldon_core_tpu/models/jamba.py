"""Jamba decoder (``model_type: jamba``, AI21-Jamba2-3B) for generative
serving: the fifth family under the contract
``executor/generation.py::GenerativeModel`` reads, and the first whose cache
is not all rows a token: 26 of its 28 layers are selective state-space mixers
whose state belongs to a SLOT and does not grow with the context, beside the
paged K/V of 2 multi-query attention layers.

One layer, ``x (T, 2560)`` (sequential pre-norm block)::

    x0 = Emb[tokens]                                        Emb (65536, 2560); the head is Emb^T (tied)
    layer l = 0..27:  attention where l % 14 == 7 (layers 7, 21), state-space elsewhere (26 layers)
    h  = RMSNorm(x; g1, eps 1e-6)

    state-space mixer (d_inner 5120 = 2 x 2560, d_state 16, d_conv 4, dt_rank 160):
      [u | z]   = h Win                                     (T, 5120 | 5120)          no bias
      c_t       = silu( sum_{j=0..3} wc[:, j] * u_{t-3+j} + bc )                      depthwise, causal, bias;
                                                             u_{t<0} = 0 in a prompt, the slot's tail in a step
      [dr|B|C]  = c Wx                                      (T, 160 | 16 | 16)        no bias
      dr = RMSNorm(dr; g_dt)   B = RMSNorm(B; g_b)   C = RMSNorm(C; g_c)              Jamba's three inner norms
      D_t       = softplus(dr_t Wdt + b_dt)                 (5120)                    float32
      A         = -exp(A_log)                               (5120, 16)                float32
      S_t       = exp(D_t[:, None] * A) * S_{t-1} + (D_t * c_t)[:, None] * B_t[None, :]     S (5120, 16) float32, S_{-1} = 0
      y_t       = S_t C_t + Dskip * c_t                     (5120)
      o         = (y * silu(z)) Wout                        (T, 2560)                 no bias
      SLOT, a layer:  S (5120, 16) float32 and the tail u_{t-2..t} (3, 5120) bfloat16.  Nothing a token.

    attention (layers 7 and 21):
      q = h Wq (T, 20, 128)   k = h Wk (T, 1, 128)   v = h Wv (T, 1, 128)             no bias, NO rotary, no position signal
      s[t,u,a] = 128^-1/2 q[t,a].k[u]   u <= t       o[t,a] = sum_u softmax_u(s) v[u]       o = concat_a(o) Wo
      POOL, a token, these two layers only:  k (128) and v (128), bfloat16: 512 B a layer, 1,024 B a token.

    x  = x + o
    h2 = RMSNorm(x; g2)
    x  = x + Wd(silu(Wg h2) * Wu h2)                        8,192 wide on all 28 layers (num_experts 1: no router)
    logits = RMSNorm(x_L; gf) Emb^T

These are the equations of the public ``jamba`` modelling code: a Mamba-1
mixer with Jamba's three inner norms, attention without any position signal, a
SwiGLU MLP.  Assumed (the configuration's file lists the same, each with its
reason): (a) the layer pattern by the family's convention, layer ``l`` is
attention where ``l % attn_layer_period == attn_layer_offset``; (b) the head
width is ``hidden / n_heads``; (c) the seeded state-space parameters follow
Mamba's published initialisation (``A_log = log(1..16)`` a channel, ``Dskip =
1``, ``b_dt`` the inverse softplus of a step drawn log-uniform in [0.001,
0.1]), because they decide whether the state matters at all; (d) the
recurrence, ``D_t``, ``A`` and the state in float32, activations and weights
in the served dtype; (e) ``num_logits_to_keep``, ``use_mamba_kernels`` and
``expert_layer_*`` under ``num_experts 1`` say nothing of a layer.

Two kinds of state for one slot (``init_paged_cache``).  Under the one block
table, ``k`` and ``v (attention layers, blocks, block, head_dim)``: the two
attention layers' rows, the uniform pool of ``models/llama.py`` with a layer
axis of 2 (``POOL_ARRAYS``).  PER SLOT and not by token (``SLOT_ARRAYS``):
``ssm (state-space layers, slots, d_state, d_inner)`` float32, the state with
its index leading (channels along the lanes: the other way round a state of
16 would be padded eightfold in HBM and fill an eighth of a register), and
``conv (state-space layers, d_conv - 1, slots, d_inner)``, the last three
inputs of the convolution, taps before slots for the same reason.  A slot
costs ``max_seq`` x 1,024 B of pool and 9.32 MB of state whatever its context.

What a program owes the slot's state: ``prefill_slot_paged`` writes ``S`` as
of the prompt's LAST REAL token and the tail ``u_{length-3..length-1}`` (zeros
before the start), overwriting what a former request left; rows of a rung
past ``length`` move neither.  ``decode_slots_paged`` advances every active
slot by one token.  An inactive slot's state is read by nothing but its own
next prefill, which overwrites it.  The state has no place in any path that
moves or shares a slot's cache: the family has no ``prefill_suffix_paged``
(prefix reuse and chunked prefill are switched off with the contract's
warning: a shared prefix would need the state AT the prefix's end, which no
block holds), no speculative verify (a rejected draft would have to rewind
the state), no LoRA, no int8 pool, no mesh, and ``generation.py::_kv_alone``
refuses handoff, suspend and the DRAM and peer tiers by the arrays' names.

Two kinds of layer in one stack by a pattern: ``params["ssm_layers"]`` and
``params["attn_layers"]`` are a stack each (every layer's MLP and norms in
its own kind's stack).  A prompt program runs the layers in their published
order in ONE scan whose body branches on the layer's kind
(``_branch_layers``: one body a kind whatever the pattern); a decode step,
whose layers update whole arrays in place, runs them as alternating runs
(``_run_layers``: a scan over each run of state-space layers, an attention
layer between).  A prompt's recurrence is ``ops/selective_scan.py`` with
``seq_impl="flash"`` (the Pallas kernel; the attention layers through
``ops/flash_attention.py``), its ``lax.scan`` reference otherwise.  A decode
step with ``kernel`` updates a layer's states IN PLACE in the carried array
(``selective_update``: each state read once and written once) and attends
through ``ops/paged_attention.py``; without it the XLA lines
(``selective_step`` on the layer taken out of the array, which read the
state twice: once for ``y``'s sum, once for the update) and the gathered
window.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax

from seldon_core_tpu.models import paged
from seldon_core_tpu.models.common import annotate_params
from seldon_core_tpu.models.layers import add, rms_head
# benchmark/reference/kinds/jamba_decoder.py reads ``_attend_prompt`` here
from seldon_core_tpu.models.layers import attend_prompt as _attend_prompt
# benchmark/reference/kinds/jamba_decoder.py reads ``_rmsnorm`` here
from seldon_core_tpu.models.layers import rmsnorm as _rmsnorm
from seldon_core_tpu.models.layers import sample_tokens  # noqa: F401  (contract)
from seldon_core_tpu.ops.selective_scan import (
    selective_scan,
    selective_scan_reference,
    selective_step,
    selective_update,
    update_group,
)

COUNTERS = (
    "ssm.prefill_tokens",  # prefill: real prompt tokens
    "ssm.prefill_rows",    # prefill: rows the prompt programs ran, padding included
    "ssm.steps",           # decode steps
    "ssm.slot_steps",      # decode: live slots summed over steps, once a step and not once a layer
                           # (each is every state-space layer's state read and written)
    "attn.rows_live",      # decode: K/V rows the attention layers HAVE to read, from the live slots'
                           # positions alone (a slot at position p attends p + 1 rows a layer),
                           # layers, slots and steps summed
)
_P_TOKENS, _P_ROWS, _STEPS, _SLOT_STEPS, _ROWS_LIVE = range(5)
# the per-token arrays of the paged pool, under the one table
POOL_ARRAYS = ("k", "v")
# the per-SLOT arrays of the cache: state that is not a row a token.  Counted
# with a slot's bytes; refused by whatever moves or shares a slot's cache
SLOT_ARRAYS = ("ssm", "conv")


@dataclasses.dataclass(frozen=True)
class Config:
    vocab_size: int = 65536
    hidden: int = 2560
    n_layers: int = 28
    n_heads: int = 20
    n_kv_heads: int = 1
    ffn: int = 8192  # intermediate_size: every layer's SwiGLU
    # the published names, as published
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    max_seq: int = 262144
    norm_eps: float = 1e-6
    # the configuration's precision of the state, stated; "bfloat16" is a
    # negative control, never served, as the four below are
    ssm_state_dtype: str = "float32"
    ssm_product_dtype: str = "float32"  # "bfloat16" rounds the recurrence's products
    ssm_padding: str = "still"  # "moves": a rung's padding rows update the state
    conv_tail_at: str = "length"  # "rung": the tail is taken at the rung's end
    dt_bias: str = "on"  # "off" leaves b_dt out of D_t

    def __post_init__(self):
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError("attn_layer_offset lies in [0, attn_layer_period)")
        if self.hidden % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads divides hidden, n_kv_heads divides n_heads")
        if self.mamba_proj_bias:
            raise ValueError("mamba_proj_bias true is not served: no projection has a bias here")
        if not 0 < self.n_attn_layers < self.n_layers:
            raise ValueError("the pattern gives no attention layer, or nothing else")
        if self.mamba_d_conv < 2:
            raise ValueError("mamba_d_conv counts the taps: two or more")
        for name, allowed in (
            ("ssm_state_dtype", ("float32", "bfloat16")),
            ("ssm_product_dtype", ("float32", "bfloat16")),
            ("ssm_padding", ("still", "moves")),
            ("conv_tail_at", ("length", "rung")),
            ("dt_bias", ("on", "off")),
        ):
            if getattr(self, name) not in allowed:
                raise ValueError(f"{name} is one of {allowed}")

    @property
    def head_dim(self) -> int:
        return self.hidden // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden

    def is_attention(self, layer: int) -> bool:
        return layer % self.attn_layer_period == self.attn_layer_offset

    @property
    def attn_layers(self) -> tuple[int, ...]:
        return tuple(l for l in range(self.n_layers) if self.is_attention(l))

    @property
    def n_attn_layers(self) -> int:
        return len(self.attn_layers)

    @property
    def n_ssm_layers(self) -> int:
        return self.n_layers - self.n_attn_layers

    @property
    def runs(self) -> tuple[tuple[bool, int, int], ...]:
        """The layers in order as runs of one kind: ``(attention, first,
        count)``, ``first`` the run's place in its own kind's stack."""
        out = []
        for kind, place in zip(map(self.is_attention, range(self.n_layers)), self.ordinals):
            if out and out[-1][0] == kind:
                out[-1][2] += 1
            else:
                out.append([kind, place, 1])
        return tuple(tuple(r) for r in out)

    @property
    def ordinals(self) -> tuple[int, ...]:
        """Each layer's place in its own kind's stack."""
        seen, out = {True: 0, False: 0}, []
        for l in range(self.n_layers):
            kind = self.is_attention(l)
            out.append(seen[kind])
            seen[kind] += 1
        return tuple(out)

    @property
    def state_dtype(self):
        return jnp.bfloat16 if self.ssm_state_dtype == "bfloat16" else jnp.float32

    @property
    def product_dtype(self):
        """None as served; the control's type to round the products to."""
        return jnp.bfloat16 if self.ssm_product_dtype == "bfloat16" else None

    @classmethod
    def tiny(cls, max_seq: int = 64, **kw) -> "Config":
        """Test-scale config: same code paths, toy sizes; two periods of
        four, attention second in each."""
        base = dict(
            vocab_size=256, hidden=64, n_layers=8, n_heads=4, n_kv_heads=1,
            ffn=96, attn_layer_period=4, attn_layer_offset=1, mamba_d_state=16,
            mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8, max_seq=max_seq,
        )
        base.update(kw)
        return cls(**base)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

DT_MIN, DT_MAX = 1e-3, 1e-1  # the step's range at initialisation (assumed (c))


def init_params(rng: jax.Array, cfg: Config, dtype=jnp.float32) -> dict:
    """Random weights IN ``dtype``, one layer at a time (a float32 tree of
    3 B parameters beside its cast would not fit a chip).  The state-space
    parameters follow Mamba's published initialisation (assumed (c)):
    ``A_log = log(1..d_state)`` a channel, ``Dskip = 1``, ``b_dt`` the
    inverse softplus of a step drawn log-uniform in [0.001, 0.1], ``Wdt``
    with a standard deviation of ``dt_rank ** -0.5``."""
    c = cfg
    keys = jax.random.split(rng, 24)
    e, f, di, n, r, k = (
        c.hidden, c.ffn, c.d_inner, c.mamba_d_state, c.mamba_dt_rank, c.mamba_d_conv,
    )
    h, kv, d = c.n_heads, c.n_kv_heads, c.head_dim
    ns, na = c.n_ssm_layers, c.n_attn_layers

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape) / math.sqrt(fan_in)).astype(dtype)

    def stacked(key, count, shape, fan_in):
        return lax.map(
            lambda l: normal(jax.random.fold_in(key, l), shape, fan_in),
            jnp.arange(count),
        )

    def rows(key, count, width, fan_in):
        """A (count, width) matrix in slabs of at most 8,192 rows."""
        slab = max(s for s in range(1, min(count, 8192) + 1) if count % s == 0)
        return lax.map(
            lambda i: normal(jax.random.fold_in(key, i), (slab, width), fan_in),
            jnp.arange(count // slab),
        ).reshape(count, width)

    def mlp(key, count):
        ks = jax.random.split(key, 3)
        ones = jnp.ones((count, e), dtype)
        return {
            "ln1": ones, "ln2": ones,
            "w_gate": stacked(ks[0], count, (e, f), e),
            "w_up": stacked(ks[1], count, (e, f), e),
            "w_down": stacked(ks[2], count, (f, e), f),
        }

    step = jnp.exp(
        jax.random.uniform(keys[0], (ns, di))
        * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN)
    )
    ssm = {
        **mlp(keys[1], ns),
        "win": stacked(keys[2], ns, (e, 2 * di), e),
        "conv_w": stacked(keys[3], ns, (k, di), k),
        # [dr | B | C] by channel: the projection's outputs lead, the
        # channels lie along the lanes (192 along them would be re-tiled whole
        # on the way into every program)
        "wx": stacked(keys[4], ns, (r + 2 * n, di), di),
        "g_dt": jnp.ones((ns, r), dtype),
        "g_b": jnp.ones((ns, n), dtype),
        "g_c": jnp.ones((ns, n), dtype),
        "wdt": stacked(keys[5], ns, (r, di), r),
        "b_dt": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
        # the state index leading, as the state is carried
        "a_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))[None, :, None],
            (ns, n, di),
        ).astype(dtype),
        "d_skip": jnp.ones((ns, di), dtype),
        "wout": stacked(keys[6], ns, (di, e), di),
    }
    if c.mamba_conv_bias:
        ssm["conv_b"] = stacked(keys[7], ns, (di,), k)
    attn = {
        **mlp(keys[8], na),
        "wq": stacked(keys[9], na, (e, h, d), e),
        "wk": stacked(keys[10], na, (e, kv, d), e),
        "wv": stacked(keys[11], na, (e, kv, d), e),
        "wo": stacked(keys[12], na, (h, d, e), h * d),
    }
    return {
        # the embedding and, read transposed, the head (tied)
        "tok_emb": rows(keys[13], c.vocab_size, e, e),
        "ssm_layers": ssm,
        "attn_layers": attn,
        "ln_f": jnp.ones((e,), dtype),
    }


_AXIS_RULES = [
    (r"layers/w(q|k|v)$", ("layers", "embed", "heads", "head_dim")),
    (r"layers/wo$", ("layers", "heads", "head_dim", "embed")),
    (r"layers/w_(gate|up)", ("layers", "embed", "mlp")),
    (r"layers/w_down", ("layers", "mlp", "embed")),
    (r"layers/win", ("layers", "embed", "mlp")),
    (r"layers/wout", ("layers", "mlp", "embed")),
    (r"layers/ln[12]", ("layers", "embed")),
    (r"tok_emb", ("vocab", "embed")),
    (r"ln_f", ("embed",)),
]


def param_logical_axes(params):
    return annotate_params(params, _AXIS_RULES)


# ---------------------------------------------------------------------------
# building blocks
# ---------------------------------------------------------------------------

def _ssm_in(h, lp, cfg: Config):
    """``[u | z] = h Win`` of ``h (..., E)``."""
    with jax.named_scope("ssm.in"):
        uz = jnp.einsum("...e,ef->...f", h, lp["win"])
        return uz[..., : cfg.d_inner], uz[..., cfg.d_inner:]


def _conv_taps(window, lp, cfg: Config):
    """``silu(sum_j wc[j] * window[j] + bc)`` of the taps ``window``, a
    sequence of ``K`` arrays ``(..., Di)``, oldest first; float32 inside, the
    activations' dtype out."""
    w = lp["conv_w"].astype(jnp.float32)
    acc = sum(window[j].astype(jnp.float32) * w[j] for j in range(cfg.mamba_d_conv))
    if cfg.mamba_conv_bias:
        acc = acc + lp["conv_b"].astype(jnp.float32)
    return jax.nn.silu(acc).astype(window[0].dtype)


def _ssm_params(c, lp, cfg: Config):
    """``D_t (..., Di)`` float32 and the normed ``B``, ``C (..., N)`` float32
    of the convolution's output ``c (..., Di)``."""
    n, r = cfg.mamba_d_state, cfg.mamba_dt_rank
    with jax.named_scope("ssm.params"):
        drbc = jnp.einsum("...d,fd->...f", c, lp["wx"])
        dr = _rmsnorm(drbc[..., :r], lp["g_dt"], cfg.norm_eps)
        b = _rmsnorm(drbc[..., r:r + n], lp["g_b"], cfg.norm_eps)
        cc = _rmsnorm(drbc[..., r + n:], lp["g_c"], cfg.norm_eps)
        pre = jnp.einsum(
            "...r,rd->...d", dr, lp["wdt"], preferred_element_type=jnp.float32
        )
        if cfg.dt_bias == "on":
            pre = pre + lp["b_dt"].astype(jnp.float32)
        return (
            jax.nn.softplus(pre), b.astype(jnp.float32), cc.astype(jnp.float32)
        )


def _a(lp):
    """``A = -exp(A_log)``, float32, the state index leading."""
    return -jnp.exp(lp["a_log"].astype(jnp.float32))


def _ssm_out(y, z, lp):
    with jax.named_scope("ssm.out"):
        gated = (
            y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        ).astype(z.dtype)
        return jnp.einsum("...d,de->...e", gated, lp["wout"])


def _ssm_prompt_parts(h, lp, cfg: Config, length, seq_impl: str) -> dict:
    """The state-space mixer over one prompt ``h (T, E)`` of ``length`` real
    tokens, part by part: ``u``, ``z``, the convolution's ``c``, ``dt``,
    ``b``, ``cc``, the recurrence's ``y (T, Di)`` and ``s (N, Di)`` float32
    as of the last real token, and ``tail (K - 1, Di)``: the convolution's
    inputs ``u`` at ``length - K + 1 .. length - 1``, zeros before the
    start."""
    T = h.shape[0]
    k = cfg.mamba_d_conv
    u, z = _ssm_in(h, lp, cfg)
    with jax.named_scope("ssm.conv"):
        padded = jnp.pad(u, ((k - 1, 0), (0, 0)))  # u_{t<0} = 0
        c = _conv_taps([padded[j:j + T] for j in range(k)], lp, cfg)
        # padded[i] is u_{i - (K - 1)}: the K - 1 rows from `length` hold
        # u_{length-K+1 .. length-1}
        at = length if cfg.conv_tail_at == "length" else T
        tail = lax.dynamic_slice_in_dim(padded, at, k - 1, axis=0)
    dt, b, cc = _ssm_params(c, lp, cfg)
    stop = length if cfg.ssm_padding == "still" else T
    with jax.named_scope("ssm.scan"):
        scan = selective_scan if seq_impl == "flash" else selective_scan_reference
        y, s = scan(
            c, dt, b, cc, _a(lp), lp["d_skip"], stop,
            product_dtype=cfg.product_dtype,
        )
    return dict(u=u, z=z, c=c, dt=dt, b=b, cc=cc, y=y, s=s, tail=tail)


def _ssm_prompt(h, lp, cfg: Config, length, seq_impl: str):
    """-> ``(o (T, E), S (N, Di) float32, tail (K - 1, Di))`` of
    :func:`_ssm_prompt_parts`."""
    p = _ssm_prompt_parts(h, lp, cfg, length, seq_impl)
    return _ssm_out(p["y"], p["z"], lp), p["s"], p["tail"]


def _ssm_step_parts(h, lp, cfg: Config, s, tail, active, *, layer=None) -> dict:
    """The mixer's one token for every slot, part by part: ``h (S, E)``,
    ``s (S, N, Di)``, ``tail (K - 1, S, Di)`` -> ``u``, ``z``, ``c``, ``dt``,
    ``b``, ``cc``, ``y (S, Di)`` float32, the new ``s`` and ``tail``.  An
    inactive slot's step counts as 0: its state stays.  With ``layer`` (a
    traced scalar), ``s`` is the whole carried array ``(layers, S, N, Di)``
    and that layer of it is updated IN PLACE by the kernel
    (``ops/selective_scan.py::selective_update``: a state read once and
    written once), the whole array handed back."""
    u, z = _ssm_in(h, lp, cfg)
    with jax.named_scope("ssm.conv"):
        window = jnp.concatenate([tail, u[None].astype(tail.dtype)])
        c = _conv_taps(window, lp, cfg)
    dt, b, cc = _ssm_params(c, lp, cfg)
    with jax.named_scope("ssm.update"):
        dt = jnp.where(active[:, None], dt, 0.0)
        if layer is None:
            y, s2 = selective_step(
                s.astype(jnp.float32), c, dt, b, cc, _a(lp), lp["d_skip"],
                product_dtype=cfg.product_dtype,
            )
            s2 = s2.astype(s.dtype)
        else:
            s2, y = selective_update(
                s, layer, c, dt, b, cc, _a(lp), lp["d_skip"],
                product_dtype=cfg.product_dtype,
            )
    return dict(u=u, z=z, c=c, dt=dt, b=b, cc=cc, y=y, s=s2, tail=window[1:])


def _ssm_step(h, lp, cfg: Config, s, tail, active, *, layer=None):
    """-> ``(o (S, E), S', tail')`` of :func:`_ssm_step_parts`."""
    p = _ssm_step_parts(h, lp, cfg, s, tail, active, layer=layer)
    return _ssm_out(p["y"].astype(p["z"].dtype), p["z"], lp), p["s"], p["tail"]


def _qkv(h, lp):
    """No bias, no rotary, no position signal of any kind."""
    with jax.named_scope("attn.qkv"):
        q = jnp.einsum("...e,ehd->...hd", h, lp["wq"])
        k = jnp.einsum("...e,ehd->...hd", h, lp["wk"])
        v = jnp.einsum("...e,ehd->...hd", h, lp["wv"])
    return q, k, v


def _after_mixer(x, o, lp, cfg: Config):
    """The rest of a layer behind its mixer's output ``o (..., E)``: the
    residual and the SwiGLU MLP, each added to the stream."""
    x = add(x, o)
    h2 = _rmsnorm(x, lp["ln2"], cfg.norm_eps)
    with jax.named_scope("mlp.gate_up"):
        act = jax.nn.silu(h2 @ lp["w_gate"]) * (h2 @ lp["w_up"])
    with jax.named_scope("mlp.down"):
        return add(x, act @ lp["w_down"])


def _attn_out(o, lp):
    with jax.named_scope("attn.out"):
        return jnp.einsum("...hd,hde->...e", o, lp["wo"])


def _head(params, x, cfg: Config):
    """Final norm and the tied head -> ``(logits, hidden)``."""
    return rms_head(x, params["ln_f"], params["tok_emb"], cfg.norm_eps)


def _pick(stack, i):
    """Layer ``i`` (traced or not) of a stack of layers' weights."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False), stack
    )


def _run_layers(params, cfg: Config, carry, ssm_fn, attn_fn):
    """The layers in their published order as runs of one kind:
    ``ssm_fn(carry, si, lp)`` over each run of state-space layers (one scan a
    run; ``si`` the layer's place in its stack), ``attn_fn(carry, ai, lp)``
    for an attention layer (``ai`` static: there are few).  What a decode
    step and the scoring pass run: their layers update whole arrays of the
    carry in place, which no branch of a ``lax.cond`` can hand through
    without copying them (:func:`_branch_layers` is the prompt's way)."""
    for attention, first, count in cfg.runs:
        stack = params["attn_layers" if attention else "ssm_layers"]
        if attention or count == 1:
            fn = attn_fn if attention else ssm_fn
            for i in range(first, first + count):
                carry = fn(carry, i, _pick(stack, i))
            continue

        def body(carry, i, stack=stack):
            return ssm_fn(carry, i, _pick(stack, i)), None

        carry, _ = lax.scan(body, carry, first + jnp.arange(count))
    return carry


def _branch_layers(params, cfg: Config, carry, layer_fn):
    """The layers in their published order in ONE scan: ``layer_fn(carry,
    attention, i) -> carry`` with ``attention`` the layer's kind (traced) and
    ``i`` its place in its own kind's stack.  The body branches on the kind
    (``lax.cond``) around what is small and writes the caches outside the
    branches: one body a kind whatever the pattern, so a prompt program is
    compiled from two layers and a trace names its kernels once each."""
    kinds = jnp.asarray([cfg.is_attention(l) for l in range(cfg.n_layers)])
    carry, _ = lax.scan(
        lambda carry, xs: (layer_fn(carry, *xs), None), carry,
        (kinds, jnp.asarray(cfg.ordinals, jnp.int32)),
    )
    return carry


# ---------------------------------------------------------------------------
# full forward (scoring; the registry's ``apply``)
# ---------------------------------------------------------------------------

def forward(params: dict, tokens: jax.Array, cfg: Config) -> jax.Array:
    """Full-sequence logits ``(B, L, V)``, one sequence after the other:
    the recurrence as its ``lax.scan``, the attention in plain XLA."""

    def one(toks):
        L = toks.shape[0]

        def ssm(x, si, lp):
            h = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
            return _after_mixer(x, _ssm_prompt(h, lp, cfg, L, "dense")[0], lp, cfg)

        def attn(x, ai, lp):
            q, k, v = _qkv(_rmsnorm(x, lp["ln1"], cfg.norm_eps), lp)
            o = _attend_prompt(q, k, v, "dense")
            return _after_mixer(x, _attn_out(o, lp), lp, cfg)

        x = _run_layers(params, cfg, params["tok_emb"][toks], ssm, attn)
        return _head(params, x, cfg)[0]

    return lax.map(one, tokens.astype(jnp.int32))


def apply(params: dict, batch: jax.Array, cfg: Config) -> jax.Array:
    """Serving entry (``JAX_MODEL``): next-token distribution."""
    return jax.nn.softmax(forward(params, batch, cfg)[:, -1].astype(jnp.float32))


# ---------------------------------------------------------------------------
# the cache: a paged pool for two layers, a state a slot for the rest
# ---------------------------------------------------------------------------

def init_paged_cache(
    cfg: Config, n_slots: int, n_blocks: int, block_size: int,
    dtype=jnp.float32, kv_sharded: bool = False, kv_dtype: str | None = None,
) -> dict:
    """Under the one table: ``k`` and ``v (attention layers, blocks,
    block_size, kv_heads * head_dim)``, the attention layers' rows and no
    other layer's; per SLOT ``ssm (state-space layers, slots, d_state,
    d_inner)`` in the stated precision of the state (float32) and ``conv
    (state-space layers, d_conv - 1, slots, d_inner)`` in ``dtype`` (the
    module's docstring has the layouts' reasons).  ``counters`` are
    ``COUNTERS``, uint32, wrapping."""
    if kv_dtype is not None:
        raise TypeError(
            f"jamba has no int8 pool (kv_cache_dtype={kv_dtype!r}): its "
            "slots' state is float32 as stated and its two attention layers' "
            "rows are a ninth of a slot"
        )
    if kv_sharded:
        raise TypeError(
            "jamba has no cache split over a mesh: its per-slot state "
            f"({', '.join(SLOT_ARRAYS)}) has no placement rule and its one "
            "key-value head no axis to split by"
        )
    row = cfg.n_kv_heads * cfg.head_dim
    pool = (cfg.n_attn_layers, n_blocks, block_size, row)
    return {
        **paged.bookkeeping(cfg.max_seq, n_slots, block_size, len(COUNTERS)),
        "k": jnp.zeros(pool, dtype),
        "v": jnp.zeros(pool, dtype),
        "ssm": jnp.zeros(
            (cfg.n_ssm_layers, n_slots, cfg.mamba_d_state, cfg.d_inner),
            cfg.state_dtype,
        ),
        "conv": jnp.zeros(
            (cfg.n_ssm_layers, cfg.mamba_d_conv - 1, n_slots, cfg.d_inner), dtype
        ),
    }


def slot_state_bytes(cfg: Config, dtype="float32") -> int:
    """HBM bytes of one slot's state, whatever its context: every
    state-space layer's ``S`` in the stated precision and its convolution
    tail in ``dtype``."""
    state = cfg.mamba_d_state * cfg.d_inner * jnp.dtype(cfg.state_dtype).itemsize
    tail = (cfg.mamba_d_conv - 1) * cfg.d_inner * jnp.dtype(dtype).itemsize
    return cfg.n_ssm_layers * (state + tail)


def paged_kv_slot_bytes(
    cfg: Config, block_size: int, *, kv_dtype: str | None = None, dtype="float32"
) -> int:
    """HBM bytes one max_seq slot costs: the two attention layers' K and V
    of every token, and the slot's state."""
    del block_size, kv_dtype
    per_token = 2 * cfg.n_kv_heads * cfg.head_dim * cfg.n_attn_layers
    return paged.slot_bytes(cfg.max_seq, per_token, dtype) + slot_state_bytes(cfg, dtype)


def prefill_slot_paged(
    params: dict, tokens: jax.Array, length: jax.Array, slot: jax.Array,
    blocks_row: jax.Array, cache: dict, cfg: Config, *, mesh=None,
    seq_impl: str = "dense", lora=None, adapter_id=None,
    return_hidden: bool = False,
):
    """Prefill ONE request's prompt (the contract of
    ``llama.prefill_slot_paged``): the attention layers' K and V go to the
    blocks reserved for ``slot``, every state-space layer's state as of the
    last real token and its convolution tail go to the SLOT, overwriting
    what a former request left.  ``seq_impl="flash"`` through the Pallas
    kernels (the recurrence and the tiled attention); ``"dense"`` through
    their XLA references."""
    del mesh, adapter_id
    paged.no_lora("jamba", lora)
    bs = cache["k"].shape[2]
    lp_ = tokens.shape[1]
    phys = blocks_row[: lp_ // bs]
    x = params["tok_emb"][tokens[0]]  # (Lp, E)

    n, di, k = cfg.mamba_d_state, cfg.d_inner, cfg.mamba_d_conv
    row = cache["k"].shape[3]
    no_rows = jnp.zeros((lp_, row), cache["k"].dtype)

    def ssm(x, i):
        lp = _pick(params["ssm_layers"], i)
        h = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
        o, s, tail = _ssm_prompt(h, lp, cfg, length, seq_impl)
        return (
            _after_mixer(x, o, lp, cfg), no_rows, no_rows,
            s.astype(cache["ssm"].dtype), tail.astype(cache["conv"].dtype),
        )

    def attn(x, i):
        lp = _pick(params["attn_layers"], i)
        q, kk, v = _qkv(_rmsnorm(x, lp["ln1"], cfg.norm_eps), lp)
        # attend what the pool will hold: the rows as stored
        kk, v = kk.astype(no_rows.dtype), v.astype(no_rows.dtype)
        o = _attend_prompt(q, kk, v, seq_impl, length=length)
        return (
            _after_mixer(x, _attn_out(o, lp), lp, cfg),
            kk.reshape(lp_, row), v.reshape(lp_, row),
            jnp.zeros((n, di), cache["ssm"].dtype),
            jnp.zeros((k - 1, di), cache["conv"].dtype),
        )

    def layer(carry, attention, i):
        x, ck, cv, cs, ct = carry
        x, kk, v, s, tail = lax.cond(attention, attn, ssm, x, i)
        # the writes, outside the branches.  A state-space layer's (zero)
        # rows go to the sink block 0; an attention layer writes the state
        # back as it found it
        ai, si = jnp.where(attention, i, 0), jnp.where(attention, 0, i)
        blocks = jnp.where(attention, phys, 0)
        ck = paged.write_prompt(ck, ai, blocks, kk, bs)
        cv = paged.write_prompt(cv, ai, blocks, v, bs)
        at = (si, slot, 0, 0)
        s = jnp.where(attention, lax.dynamic_slice(cs, at, (1, 1, n, di))[0, 0], s)
        cs = lax.dynamic_update_slice(cs, s[None, None], at)
        # a row a tap: one update of all three would have the compiler
        # re-lay the whole array with the taps along the sublanes
        for j in range(k - 1):
            at = (si, j, slot, 0)
            row_j = jnp.where(
                attention, lax.dynamic_slice(ct, at, (1, 1, 1, di))[0, 0, 0], tail[j]
            )
            ct = lax.dynamic_update_slice(ct, row_j[None, None, None], at)
        return x, ck, cv, cs, ct

    x, ck, cv, cs, ct = _branch_layers(
        params, cfg, (x, cache["k"], cache["v"], cache["ssm"], cache["conv"]), layer
    )
    ctr = paged.bump(cache.get("counters"), _P_TOKENS, length)
    ctr = paged.bump(ctr, _P_ROWS, lp_)
    out = dict(cache)
    out.update(
        k=ck, v=cv, ssm=cs, conv=ct,
        pos=cache["pos"].at[slot].set(length),
        table=cache["table"].at[slot].set(blocks_row),
    )
    if ctr is not None:
        out["counters"] = ctr
    h = lax.dynamic_index_in_dim(x, length - 1, axis=0, keepdims=False)
    logits, h = _head(params, h, cfg)
    if return_hidden:
        return logits, out, h
    return logits, out


def decode_slots_paged(
    params: dict, tokens: jax.Array, cache: dict, active: jax.Array,
    cfg: Config, *, window: int | None = None, kernel: bool = False,
    lora=None, adapter_ids=None, kv_sharded: bool = False,
):
    """One decode step for every slot (the contract of
    ``llama.decode_slots_paged``): every state-space layer's tail shifted
    and state updated in float32, the attention layers' K and V written to
    the pool and read through the table.  ``window`` (static) bounds the
    table's columns read; ``kernel`` (static) reads through the Pallas paged
    kernel, each slot's live blocks alone."""
    del adapter_ids, kv_sharded
    paged.no_lora("jamba", lora)
    pos = cache["pos"]
    S = tokens.shape[0]
    bs = cache["k"].shape[2]
    write_blk, write_off, read_blk = paged.decode_frame(
        cache, active, bs, window, cfg.max_seq
    )
    x = params["tok_emb"][tokens]  # (S, E)

    # with the kernels, a layer's states are updated in place in the carried
    # array; the XLA lines take the layer out and put it back
    in_place = kernel and update_group(S) is not None

    def ssm(carry, si, lp):
        x, ck, cv, cs, ct = carry
        h = _rmsnorm(x, lp["ln1"], cfg.norm_eps)
        tail = lax.dynamic_index_in_dim(ct, si, keepdims=False)
        if in_place:
            o, cs, tail = _ssm_step(h, lp, cfg, cs, tail, active, layer=si)
        else:
            o, s, tail = _ssm_step(
                h, lp, cfg, lax.dynamic_index_in_dim(cs, si, keepdims=False),
                tail, active,
            )
            cs = lax.dynamic_update_index_in_dim(cs, s, si, 0)
        ct = lax.dynamic_update_index_in_dim(ct, tail, si, 0)
        return _after_mixer(x, o, lp, cfg), ck, cv, cs, ct

    def attn(carry, ai, lp):
        x, ck, cv, cs, ct = carry
        q, k, v = _qkv(_rmsnorm(x, lp["ln1"], cfg.norm_eps), lp)
        ck = ck.at[ai, write_blk, write_off].set(k.reshape(S, -1).astype(ck.dtype))
        cv = cv.at[ai, write_blk, write_off].set(v.reshape(S, -1).astype(cv.dtype))
        o = paged.attend_paged(q, ck, cv, ai, read_blk, pos, active, kernel=kernel)
        return _after_mixer(x, _attn_out(o, lp), lp, cfg), ck, cv, cs, ct

    x, ck, cv, cs, ct = _run_layers(
        params, cfg, (x, cache["k"], cache["v"], cache["ssm"], cache["conv"]),
        ssm, attn,
    )
    ctr = paged.bump(cache.get("counters"), _STEPS, 1)
    ctr = paged.bump(ctr, _SLOT_STEPS, jnp.sum(active))
    ctr = paged.bump(
        ctr, _ROWS_LIVE, cfg.n_attn_layers * jnp.sum(jnp.where(active, pos + 1, 0))
    )
    out = dict(cache)
    out.update(k=ck, v=cv, ssm=cs, conv=ct, pos=jnp.where(active, pos + 1, pos))
    if ctr is not None:
        out["counters"] = ctr
    return _head(params, x, cfg)[0], out

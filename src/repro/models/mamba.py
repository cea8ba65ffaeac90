"""Mamba-1 (falcon-mamba) and Mamba-2/SSD + shared-attention hybrid (zamba2).

MixFP4 applies to the projection GEMMs (in/out/x/dt projections — see
DESIGN.md §Arch-applicability); the SSM recurrences themselves are not GEMMs
and stay in high precision, mirroring the paper's treatment of attention and
nonlinearities.  At serve time the same boundary carries the W4A4 mode:
``Ctx(act_quant="mixfp4")`` makes every packed-weight ``qlinear`` (and the
hybrid's shared-attention projections) quantize its activation rows and run
the W4A4 kernel — the recurrent state stays f32 throughout
(docs/serving.md).

Selective scans are *chunked*: the (B, chunk, d_inner, N) state tensor is the
only materialisation (Mamba-1), or the SSD chunked form with its (B, c, c, H)
intra-chunk decay matrix (Mamba-2) — both bounded by cfg.ssm_chunk and
sharded over the model axis on channels/heads.  Decode is the same math at
chunk length 1 with O(1) carried state — which is what makes the SSM archs
the `long_500k` candidates.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import qtensor
from repro.models import base
from repro.models.base import ArchConfig, Ctx, Param, qlinear, rms_norm, shard, unzip_params


def _app_take(c, aidx):
    """Slice attention-app ``aidx`` off a stacked KV carry.  The carry is
    either a dense (na, B, S, H, dh) array or a packed QTensor whose
    children lead with the app axis — scan carries can't be sliced as xs
    because only every ``attn_period``-th layer applies the shared block."""
    take = lambda a: jax.lax.dynamic_index_in_dim(a, aidx, 0, keepdims=False)
    if isinstance(c, qtensor.QTensor):
        return qtensor.QTensor(take(c.payload), take(c.scales),
                               take(c.scale32), c.method, c.layout,
                               c.shape, c.dtype)
    return take(c)


def _app_put(c, new, aidx):
    """Write app ``aidx``'s updated KV back into the stacked carry."""
    put = lambda a, n: jax.lax.dynamic_update_index_in_dim(
        a, n.astype(a.dtype), aidx, 0)
    if isinstance(c, qtensor.QTensor):
        # scale32 is pinned (base.KV_SCALE32) and shared across apps
        return qtensor.QTensor(put(c.payload, new.payload),
                               put(c.scales, new.scales), c.scale32,
                               c.method, c.layout, c.shape, c.dtype)
    return put(c, new)


# ---------------------------------------------------------------------------
# shared scan helpers
# ---------------------------------------------------------------------------
def _assoc_combine(c1, c2):
    a1, b1 = c1
    a2, b2 = c2
    return a2 * a1, a2 * b1 + b2


def selective_scan_m1(x, dt, A, Bm, Cm, h0, chunk: int):
    """Mamba-1 selective scan, chunked.

    x, dt: (B,S,Di); A: (Di,N); Bm, Cm: (B,S,N); h0: (B,Di,N) f32.
    Returns (y (B,S,Di), hT)."""
    b, s, di = x.shape
    n = A.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    def step(h, inp):
        xc, dtc, bc, cc = inp                    # (B,c,Di) / (B,c,N)
        a = jnp.exp(dtc[..., None] * A)          # (B,c,Di,N)
        bx = (dtc * xc)[..., None] * bc[:, :, None, :]
        aa, bb = jax.lax.associative_scan(_assoc_combine, (a, bx), axis=1)
        h_all = aa * h[:, None] + bb             # (B,c,Di,N)
        y = jnp.einsum("bcdn,bcn->bcd", h_all, cc)
        return h_all[:, -1], y

    xs = jax.tree.map(
        lambda t: t.reshape(b, nc, chunk, *t.shape[2:]).swapaxes(0, 1),
        (x.astype(jnp.float32), dt.astype(jnp.float32),
         Bm.astype(jnp.float32), Cm.astype(jnp.float32)))
    step_fn = jax.checkpoint(step) if nc > 1 else step
    hT, ys = jax.lax.scan(step_fn, h0, xs)
    y = ys.swapaxes(0, 1).reshape(b, s, di)
    return y, hT


def ssd_scan_m2(x, dt, A, Bm, Cm, h0, chunk: int):
    """Mamba-2 SSD chunked scan.

    x: (B,S,H,P); dt: (B,S,H); A: (H,) (negative); Bm, Cm: (B,S,N);
    h0: (B,H,P,N).  Returns (y (B,S,H,P), hT)."""
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    def step(hst, inp):
        xc, dtc, bc, cc = inp                    # (B,c,H,P) (B,c,H) (B,c,N)
        la = dtc * A                             # log decay per step (B,c,H)
        lcum = jnp.cumsum(la, axis=1)            # l_t
        # intra-chunk: y[t] += sum_{s<=t} exp(l_t - l_s) dt_s (C_t.B_s) x_s
        decay = jnp.exp(lcum[:, :, None, :] - lcum[:, None, :, :])  # (B,c,c,H)
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        decay = jnp.where(tri[None, :, :, None], decay, 0.0)
        scores = jnp.einsum("btn,bsn->bts", cc, bc)          # (B,c,c)
        g = scores[..., None] * decay                        # (B,c,c,H)
        y_in = jnp.einsum("btsh,bsh,bshp->bthp", g, dtc, xc)
        # inter-chunk: y[t] += exp(l_t) C_t . h0
        y_x = jnp.einsum("btn,bhpn->bthp", cc, hst) * jnp.exp(lcum)[..., None]
        # state update: h' = exp(l_last) h0 + sum_s exp(l_last-l_s) dt_s x_s B_s
        w = jnp.exp(lcum[:, -1:, :] - lcum) * dtc            # (B,c,H)
        h_new = (hst * jnp.exp(lcum[:, -1])[:, :, None, None]
                 + jnp.einsum("bsh,bshp,bsn->bhpn", w, xc, bc))
        return h_new, y_in + y_x

    xs = jax.tree.map(
        lambda t: t.reshape(b, nc, chunk, *t.shape[2:]).swapaxes(0, 1),
        (x.astype(jnp.float32), dt.astype(jnp.float32),
         Bm.astype(jnp.float32), Cm.astype(jnp.float32)))
    step_fn = jax.checkpoint(step) if nc > 1 else step
    hT, ys = jax.lax.scan(step_fn, h0, xs)
    y = ys.swapaxes(0, 1).reshape(b, s, h, p)
    return y, hT


def causal_conv(x, w, bias, state=None):
    """Depthwise causal conv along S.  x: (B,S,C); w: (K,C); state: (B,K-1,C)
    carries the last K-1 inputs for decode.  Returns (y, new_state)."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k))
    new_state = xp[:, -(k - 1):] if k > 1 else None
    return y + bias, new_state


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------
class MambaLM:
    """families: 'ssm' (mamba1 stack) and 'hybrid' (mamba2 + shared attn)."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.d_inner = cfg.ssm_expand * cfg.d_model
        self.dt_rank = max(cfg.d_model // 16, 1)
        if cfg.ssm_version == 2:
            self.n_ssm_heads = self.d_inner // cfg.ssm_head_dim

    # -- layer params ---------------------------------------------------
    def _layer_init(self, key):
        cfg = self.cfg
        di, n = self.d_inner, cfg.ssm_state
        ks = jax.random.split(key, 6)
        s = 1.0 / math.sqrt(cfg.d_model)
        p = {"ln": base.norm_init(cfg.d_model)}
        if cfg.ssm_version == 1:
            r = self.dt_rank
            p.update({
                "in_proj": base.linear_init(ks[0], cfg.d_model, 2 * di),
                "conv_w": Param(jax.random.normal(ks[1], (cfg.ssm_conv, di),
                                                  jnp.float32) * 0.2,
                                P(None, "model")),
                "conv_b": Param(jnp.zeros((di,)), P("model")),
                "x_proj": base.linear_init(ks[2], di, r + 2 * n,
                                           spec=P("model", None)),
                "dt_proj": base.linear_init(ks[3], r, di,
                                            spec=P(None, "model")),
                "dt_bias": Param(jnp.log(jnp.expm1(
                    jnp.clip(jax.random.uniform(ks[4], (di,)) * 0.1,
                             1e-3, None))), P("model")),
                "A_log": Param(jnp.log(jnp.tile(
                    jnp.arange(1, n + 1, dtype=jnp.float32), (di, 1))),
                    P("model", None)),
                "Dskip": Param(jnp.ones((di,)), P("model")),
                "out_proj": base.linear_init(ks[5], di, cfg.d_model,
                                             spec=P("model", None)),
            })
        else:
            h = self.n_ssm_heads
            d_in = 2 * di + 2 * n + h    # [z, x, B, C, dt]
            p.update({
                "in_proj": base.linear_init(ks[0], cfg.d_model, d_in),
                "conv_w": Param(jax.random.normal(
                    ks[1], (cfg.ssm_conv, di + 2 * n), jnp.float32) * 0.2,
                    P(None, "model")),
                "conv_b": Param(jnp.zeros((di + 2 * n,)), P("model")),
                "dt_bias": Param(jnp.full((h,), -2.0), P("model")),
                "A_log": Param(jnp.zeros((h,)), P("model")),
                "Dskip": Param(jnp.ones((h,)), P("model")),
                "ssm_norm": base.norm_init(di),
                "out_proj": base.linear_init(ks[5], di, cfg.d_model,
                                             spec=P("model", None)),
            })
        return p

    def _shared_attn_init(self, key):
        """Zamba2-style shared transformer block on concat(x, x_embed)."""
        cfg = self.cfg
        d2 = 2 * cfg.d_model
        ks = jax.random.split(key, 3)
        acfg = cfg.replace(qk_norm=False)
        return {
            "ln_attn": base.norm_init(d2),
            "attn": base.attn_init(ks[0], acfg, d_in=d2),
            "ln_mlp": base.norm_init(d2),
            "mlp": base.mlp_init(ks[1], cfg, d_ff=cfg.d_ff, d_in=d2),
        }

    def init(self, key):
        cfg = self.cfg
        ke, kl, ka = jax.random.split(key, 3)
        proto = self._layer_init(kl)
        _, lsp = unzip_params(proto)
        layer_specs = jax.tree.map(lambda sp: P(None, *sp), lsp)
        lkeys = jax.random.split(kl, cfg.n_layers)
        layer_values = jax.vmap(
            lambda k: unzip_params(self._layer_init(k))[0])(lkeys)
        values = {
            "embed": jax.random.normal(ke, (base.padded_vocab(cfg.vocab), cfg.d_model),
                                       jnp.float32) * 0.02,
            "layers": layer_values,
            "ln_f": jnp.ones((cfg.d_model,), jnp.float32),
        }
        specs = {"embed": P("model", None), "layers": layer_specs,
                 "ln_f": P(None)}
        if cfg.attn_period:
            sa_v, sa_s = unzip_params(self._shared_attn_init(ka))
            values["shared_attn"] = sa_v
            specs["shared_attn"] = sa_s
        return values, specs

    # -- SSM block forward ------------------------------------------------
    def _block(self, lp, x, ctx: Ctx, h0, conv0):
        """x: (B,S,D).  Returns (out, hT, convT)."""
        cfg = self.cfg
        di, n = self.d_inner, cfg.ssm_state
        h = rms_norm(x, lp["ln"], cfg.norm_eps)
        if cfg.ssm_version == 1:
            xz = qlinear(h, lp["in_proj"], ctx, 0)
            xz = shard(xz, "data", None, "model")
            xs, z = jnp.split(xz, 2, axis=-1)
            xs, convT = causal_conv(xs, lp["conv_w"], lp["conv_b"], conv0)
            xs = jax.nn.silu(xs)
            proj = qlinear(xs, lp["x_proj"], ctx, 1)
            dt_raw, bm, cm = jnp.split(
                proj, [self.dt_rank, self.dt_rank + n], axis=-1)
            dt = jax.nn.softplus(
                qlinear(dt_raw, lp["dt_proj"], ctx, 2) + lp["dt_bias"])
            A = -jnp.exp(lp["A_log"].astype(jnp.float32))
            y, hT = selective_scan_m1(xs, dt, A, bm, cm, h0, cfg.ssm_chunk)
            y = (y + xs.astype(jnp.float32) * lp["Dskip"]).astype(x.dtype)
            y = y * jax.nn.silu(z)
            out = qlinear(y, lp["out_proj"], ctx, 3)
        else:
            nh = self.n_ssm_heads
            zxbcdt = qlinear(h, lp["in_proj"], ctx, 0)
            zxbcdt = shard(zxbcdt, "data", None, "model")
            z, xbc, dt_raw = jnp.split(zxbcdt, [di, 2 * di + 2 * n], axis=-1)
            xbc, convT = causal_conv(xbc, lp["conv_w"], lp["conv_b"], conv0)
            xbc = jax.nn.silu(xbc)
            xs, bm, cm = jnp.split(xbc, [di, di + n], axis=-1)
            dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + lp["dt_bias"])
            A = -jnp.exp(lp["A_log"].astype(jnp.float32))
            xh = xs.reshape(*xs.shape[:2], nh, cfg.ssm_head_dim)
            y, hT = ssd_scan_m2(xh, dt, A, bm, cm, h0, cfg.ssm_chunk)
            y = y + xh.astype(jnp.float32) * lp["Dskip"][:, None]
            y = y.reshape(*xs.shape).astype(x.dtype)
            y = rms_norm(y * jax.nn.silu(z), lp["ssm_norm"], cfg.norm_eps)
            out = qlinear(y, lp["out_proj"], ctx, 3)
        return x + out, hT, convT

    def _shared_block(self, sp, x, x0, ctx: Ctx, *, positions,
                      kv_cache=None, cache_len=None, block_tables=None):
        """Zamba2 shared attn+MLP on concat(x, x_embed); output added to x."""
        cfg = self.cfg
        d2 = 2 * cfg.d_model
        acfg = cfg.replace(qk_norm=False)
        h2 = jnp.concatenate([x, x0], axis=-1)
        hn = rms_norm(h2, sp["ln_attn"], cfg.norm_eps)
        attn_out, new_cache = base.attn_apply(
            sp["attn"], hn, ctx.fold(7), acfg, positions=positions,
            window=0, kv_cache=kv_cache, cache_len=cache_len,
            block_tables=block_tables)
        x = x + attn_out
        h2 = jnp.concatenate([x, x0], axis=-1)
        hn = rms_norm(h2, sp["ln_mlp"], cfg.norm_eps)
        x = x + base.mlp(sp["mlp"], hn, ctx.fold(8), cfg)
        return x, new_cache

    # -- layer-stack drivers ----------------------------------------------
    def _attn_flags(self):
        cfg = self.cfg
        flags = np.zeros((cfg.n_layers,), bool)
        if cfg.attn_period:
            flags[0::cfg.attn_period] = True
        return flags, np.maximum(np.cumsum(flags) - 1, 0).astype(np.int32)

    def n_attn_apps(self) -> int:
        return int(self._attn_flags()[0].sum())

    def layer_windows(self) -> np.ndarray:
        """The window of each application of the shared attention block:
        all full (0), as ``_shared_block`` calls it."""
        return np.zeros((self.n_attn_apps(),), np.int32)

    def _init_states(self, batch: int):
        cfg = self.cfg
        di, n = self.d_inner, cfg.ssm_state
        if cfg.ssm_version == 1:
            h = jnp.zeros((cfg.n_layers, batch, di, n), jnp.float32)
        else:
            h = jnp.zeros((cfg.n_layers, batch, self.n_ssm_heads,
                           cfg.ssm_head_dim, n), jnp.float32)
        cw = di if cfg.ssm_version == 1 else di + 2 * n
        conv = jnp.zeros((cfg.n_layers, batch, cfg.ssm_conv - 1, cw),
                         jnp.bfloat16)
        return h, conv

    def _run_layers(self, params, x, ctx: Ctx, h0s, conv0s, *, positions,
                    kv_cache=None, cache_len=None, block_tables=None):
        cfg = self.cfg
        flags, app_idx = self._attn_flags()
        lkeys = jax.random.split(ctx.key, cfg.n_layers)
        x0 = x
        sp = params.get("shared_attn")
        use_cache = kv_cache is not None

        def body(carry, xs_in):
            x, kc, vc = carry
            lp, lk, h0, c0, flag, aidx = xs_in
            lctx = ctx.with_key(lk)
            x, hT, convT = self._block(lp, x, lctx, h0, c0)
            x = shard(x, "data", None, "model")  # D-sharded residual carry

            if sp is not None:
                def with_attn(x):
                    if use_cache:
                        kci = _app_take(kc, aidx)
                        vci = _app_take(vc, aidx)
                        xo, ncache = self._shared_block(
                            sp, x, x0, lctx, positions=positions,
                            kv_cache=(kci, vci), cache_len=cache_len,
                            block_tables=block_tables)
                        nkc = _app_put(kc, ncache[0], aidx)
                        nvc = _app_put(vc, ncache[1], aidx)
                        return xo, nkc, nvc
                    xo, _ = self._shared_block(sp, x, x0, lctx,
                                               positions=positions)
                    return xo, kc, vc

                x, kc, vc = jax.lax.cond(
                    flag, with_attn, lambda x: (x, kc, vc), x)
            return (x, kc, vc), (hT, convT)

        body_fn = jax.checkpoint(body) if cfg.n_layers > 1 else body
        kc0 = kv_cache[0] if use_cache else jnp.zeros((1,), jnp.bfloat16)
        vc0 = kv_cache[1] if use_cache else jnp.zeros((1,), jnp.bfloat16)
        (x, kc, vc), (hTs, convTs) = jax.lax.scan(
            body_fn, (x, kc0, vc0),
            (params["layers"], lkeys, h0s, conv0s,
             jnp.asarray(flags), jnp.asarray(app_idx)))
        return x, hTs, convTs, (kc, vc)

    # -- public API ---------------------------------------------------------
    def hidden(self, params, batch, ctx: Ctx):
        cfg = self.cfg
        x = params["embed"][batch["tokens"]].astype(jnp.bfloat16)
        x = shard(x, "data", None, "model")
        b, s = batch["tokens"].shape
        h0s, conv0s = self._init_states(b)
        positions = jnp.arange(s)[None, :]
        x, _, _, _ = self._run_layers(params, x, ctx, h0s, conv0s,
                                      positions=positions)
        return rms_norm(x, params["ln_f"], cfg.norm_eps), 0.0

    def forward(self, params, batch, ctx: Ctx):
        x, aux = self.hidden(params, batch, ctx)
        logits = base.lm_logits(x, params["embed"], self.cfg.softcap_final)
        return base.shard(logits, "data", None, "model"), aux

    def loss(self, params, batch, ctx: Ctx):
        x, aux = self.hidden(params, batch, ctx)
        return base.fused_lm_loss(x, params["embed"], batch["labels"],
                                  self.cfg.softcap_final,
                                  self.cfg.vocab) + aux

    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16,
                   kv_quant: str | None = None,
                   pages: tuple[int, int] | None = None):
        """Recurrent h/conv state plus (hybrid) the shared-attention KV
        cache.  ``kv_quant="mixfp4"`` packs the KV exactly like the
        transformer families — QTensor children with a leading *app* axis
        ((na, B, S, H, dh//2) payload + scale bytes) that ``_app_take``
        slices per shared-block application — and ``pages=(num_pages,
        page_len)`` swaps the per-slot stripes for pool page slabs
        ((na, P, page_len, H, ...)) plus a ``"pages"`` block table, so the
        hybrid rides the same serving.kvpool as the transformers.  The
        h/conv state stays dense f32/bf16 per slot either way: SSM state
        is not attention history and cannot be paged or prefix-shared."""
        cfg = self.cfg
        h, conv = self._init_states(batch_size)
        cache = {"h": h, "conv": conv}
        if pages is not None and not cfg.attn_period:
            raise ValueError("paged KV (pages=) needs a hybrid arch with "
                             "shared attention (cfg.attn_period)")
        if not cfg.attn_period:
            return cache
        na = self.n_attn_apps()
        if kv_quant is None or kv_quant == "bf16":
            if pages is not None:
                raise ValueError("paged KV (pages=) requires "
                                 f"kv_quant='mixfp4', got {kv_quant!r}")
            shape = (na, batch_size, max_len, cfg.n_heads, cfg.dh)
            cache["k"] = jnp.zeros(shape, dtype)
            cache["v"] = jnp.zeros(shape, dtype)
            return cache
        if kv_quant != "mixfp4":
            raise ValueError(f"unknown kv_quant {kv_quant!r} "
                             "(expected None, 'bf16' or 'mixfp4')")
        if cfg.dh % 16:
            raise ValueError(
                f"kv_quant='mixfp4' needs head_dim % 16 == 0, got {cfg.dh}")
        if pages is not None:
            num_pages, page_len = pages
            if page_len % 16 or max_len % page_len:
                raise ValueError(
                    f"page_len={page_len} must be a multiple of 16 and "
                    f"divide max_len={max_len}")
            rows = (num_pages, page_len, cfg.n_heads)
        else:
            rows = (batch_size, max_len, cfg.n_heads)

        def packed():
            return qtensor.QTensor(
                jnp.zeros((na, *rows, cfg.dh // 2), jnp.uint8),
                jnp.zeros((na, *rows, cfg.dh // 16), jnp.uint8),
                jnp.full((na,), base.KV_SCALE32, jnp.float32),
                method="mixfp4", layout=qtensor.BlockLayout1D(-1, 16),
                shape=(*rows, cfg.dh), dtype="float32")

        cache["k"] = packed()
        cache["v"] = packed()
        if pages is not None:
            cache["pages"] = jnp.zeros(
                (batch_size, max_len // page_len), jnp.int32)
        return cache

    def cache_specs(self):
        cfg = self.cfg
        specs = {
            "h": P(None, "data", "model", None) if cfg.ssm_version == 1
            else P(None, "data", "model", None, None),
            "conv": P(None, "data", None, "model"),
        }
        if cfg.attn_period:
            # zamba2 shared-attn cache shards over HEADS (32 % 16 == 0):
            # a 1-token dynamic-update on a seq-sharded dim would force
            # GSPMD to gather the 500k cache
            specs["k"] = P(None, "data", None, "model", None)
            specs["v"] = P(None, "data", None, "model", None)
        return specs

    def prefill(self, params, batch, ctx: Ctx, cache, block_tables=None):
        cfg = self.cfg
        x = params["embed"][batch["tokens"]].astype(jnp.bfloat16)
        x = shard(x, "data", None, None)
        b, s = batch["tokens"].shape
        positions = jnp.arange(s)[None, :]
        kv = (cache["k"], cache["v"]) if cfg.attn_period else None
        x, hTs, convTs, kvT = self._run_layers(
            params, x, ctx, cache["h"], cache["conv"],
            positions=positions, kv_cache=kv, cache_len=0 if kv else None,
            block_tables=block_tables)
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = base.lm_logits(x[:, -1], params["embed"], cfg.softcap_final, vocab=cfg.vocab)
        new_cache = {"h": hTs, "conv": convTs}
        if cfg.attn_period:
            new_cache["k"], new_cache["v"] = kvT
        return logits, new_cache

    def reset_slot(self, cache, i: int):
        """Zero slot ``i``'s recurrent SSM state, conv window and (hybrid)
        K/V rows — for the SSM a zeroed state IS the fresh-request state.
        Paged caches zero only the slot's h/conv rows and block-table row
        (all entries -> trash page 0); pool pages belong to the pool."""
        if isinstance(cache, dict) and "pages" in cache:
            return dict(cache,
                        h=cache["h"].at[:, i].set(0),
                        conv=cache["conv"].at[:, i].set(0),
                        pages=cache["pages"].at[i].set(0))
        return base._map_slot_arrays(lambda a: a.at[:, i].set(0), cache)

    def slot_state(self, cache, i: int):
        """Snapshot slot ``i``'s rows (fixed-slot caches only).  Unlike KV
        rows, the recurrent h/conv state advances for EVERY batch row each
        decode step, so the engine must restore other active slots after a
        prefill — a dummy step is irreversible for an SSM."""
        assert "pages" not in cache, "paged caches have no per-slot KV rows"
        return base._map_slot_arrays(lambda a: a[:, i], cache)

    def write_slot(self, cache, i: int, state):
        assert "pages" not in cache, "paged caches have no per-slot KV rows"
        return base._map_slot_arrays(
            lambda a, s: a.at[:, i].set(s.astype(a.dtype)), cache, state)

    def prefill_slot(self, params, tokens, ctx: Ctx, cache, slot,
                     true_len=None, start_pos=None):
        """Batched single-slot prefill: slice the cache to the slot's batch
        row, run the whole prompt through the chunked-scan prefill in ONE
        call, and scatter the row back.  Only slot ``slot``'s recurrent
        state advances — the dummy-step corruption that forced the engine's
        snapshot/restore dance around admissions cannot happen.  Returns
        (last-position logits (1, V), updated full cache)."""
        if true_len is not None:
            raise ValueError(
                "prompt-length bucketing (true_len) is transformer-only: "
                "the SSM recurrent state advances for every padded suffix "
                "token, so a bucketed prompt would corrupt the slot state")
        if start_pos is not None:
            raise ValueError(
                "chunked/suffix prefill (start_pos) is transformer-only: "
                "resuming an SSM prompt mid-way needs the recurrent state "
                "checkpointed at the chunk boundary, which this cache does "
                "not carry (ROADMAP carry-over) — prefill hybrids from "
                "position 0 in one call")
        cfg = self.cfg
        p_len = tokens.shape[1]
        # chunked scans/attention need p_len % chunk == 0 once p_len exceeds
        # the chunk; awkward prompt lengths fall back to one unchunked block
        # (p_len is a static shape — each length compiles its own prefill)
        cfg2 = cfg
        if p_len > cfg.ssm_chunk and p_len % cfg.ssm_chunk:
            cfg2 = cfg2.replace(ssm_chunk=p_len)
        if cfg.attn_period and p_len > cfg.attn_chunk \
                and p_len % cfg.attn_chunk:
            cfg2 = cfg2.replace(attn_chunk=p_len)
        model = self if cfg2 is cfg else MambaLM(cfg2)
        if isinstance(cache, dict) and "pages" in cache:
            # paged: slice only the slot's recurrent state; the KV pool
            # stays whole and the slot's block-table row routes the writes.
            # No start_pos/prefix sharing for hybrids: the SSM state needs
            # the full prompt run regardless, so engines admit hybrids with
            # prefix caching disabled and always prefill from position 0.
            recur = {"h": cache["h"], "conv": cache["conv"]}
            small = base.slot_take(recur, slot)
            small["k"], small["v"] = cache["k"], cache["v"]
            btrow = jax.lax.dynamic_slice_in_dim(cache["pages"], slot, 1,
                                                 axis=0)
            logits, new_small = model.prefill(
                params, {"tokens": tokens}, ctx, small, block_tables=btrow)
            out = base.slot_put(
                recur, {"h": new_small["h"], "conv": new_small["conv"]},
                slot)
            return logits, {"h": out["h"], "conv": out["conv"],
                            "k": new_small["k"], "v": new_small["v"],
                            "pages": cache["pages"]}
        small = base.slot_take(cache, slot)
        logits, new_small = model.prefill(
            params, {"tokens": tokens}, ctx, small)
        return logits, base.slot_put(cache, new_small, slot)

    def decode_step(self, params, tokens, ctx: Ctx, cache, cache_len):
        cfg = self.cfg
        x = params["embed"][tokens[:, None]].astype(jnp.bfloat16)
        positions = base.decode_positions(cache_len, x.shape[0])
        kv = (cache["k"], cache["v"]) if cfg.attn_period else None
        bt = cache.get("pages") if isinstance(cache, dict) else None
        x, hTs, convTs, kvT = self._run_layers(
            params, x, ctx, cache["h"], cache["conv"],
            positions=positions, kv_cache=kv,
            cache_len=cache_len if kv else None, block_tables=bt)
        x = rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = base.lm_logits(x[:, 0], params["embed"], cfg.softcap_final, vocab=cfg.vocab)
        new_cache = {"h": hTs, "conv": convTs}
        if cfg.attn_period:
            new_cache["k"], new_cache["v"] = kvT
        if bt is not None:
            new_cache["pages"] = cache["pages"]
        return logits, new_cache

"""Latent decode attention's share of its memory roofline: the bytes the
latent attention of every decode token that reached its client in the
traced window must read (its LM module's ``latent_attn_bytes`` at the
token's cached length, over every layer), over the time the
``decode_attn_latent`` kernel ran on the chip times the chip's HBM peak.

The byte count is a lower bound of what the kernel reads, which streams
whole blocks of rows (and the rows of a wave's padding), so the share
cannot pass 100%. None where the trace holds no such kernel or the LM
module counts no latent bytes."""
import devtrace
import spans


def read(ctx):
    if not ctx.win or not ctx.planes or ctx.peak is None:
        return None
    per_token = getattr(ctx.lm, "latent_attn_bytes", None)
    if per_token is None:
        return None
    lo, hi = ctx.win
    busy_ns = 0.0
    for p in ctx.planes:
        ops = [e for e in ctx.trace[p][devtrace.OPS]
               if spans.kernel(e[0]) == "decode_attn_latent"]
        busy_ns += sum(b - a for a, b in
                       devtrace._union(devtrace._clip(ops, lo, hi)))
    if not busy_ns:
        return None
    model = ctx.cfg["model"]
    need = sum(per_token(model, len(r.request.prompt) + j)
               for r, j, _ in ctx.tokens(ctx.t0, ctx.t_end) if j > 0)
    return 100.0 * need / (busy_ns * 1e-9 * ctx.peak["hbm_bytes_per_s"])

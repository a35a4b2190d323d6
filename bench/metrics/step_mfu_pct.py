"""Whole-step model FLOP/s utilization: the operations every token that
reached a client in the traced window required (its LM step or, for a
first token, the prompt's prefill, as the configuration's LM module
counts them; plus its query's probe and distance tables), over the
window and the chips' bf16 peak."""
import work


def read(ctx):
    if not ctx.win or ctx.peak is None:
        return None
    model, ds = ctx.cfg["model"], ctx.cfg["datastore"]
    per_query = work.retrieval_token(ds, model["d_model"])
    ops = 0.0
    for r, j, _ in ctx.tokens(ctx.t0, ctx.t_end):
        t0 = len(r.request.prompt)
        ops += per_query + (ctx.lm.prefill(model, t0) if j == 0
                            else ctx.lm.lm_token(model, t0 + j))
    lo, hi = ctx.win
    return 100.0 * ops / ((hi - lo) * 1e-9) / (ctx.chips *
                                               ctx.peak["bf16_flops"])

"""The decode program's share of the chip's memory bandwidth for the
``jamba`` family: the bytes a decode-program step cannot avoid
(``counts_jamba.decode_step_bytes``: every weight once, the embedding as
the head; the live slots' recurrent state read and written; the live
K/V), summed over the window's decode-program steps, over their
host-clock seconds x peak bandwidth."""

from benchmarks import counts_jamba as counts


def read(ctx):
    cfg = ctx["cfg"]
    steps = [s for s in ctx["record"]["steps"]
             if not s.mixed and s.decode_contexts]
    seconds = sum(s.t1 - s.t0 for s in steps)
    if seconds <= 0:
        return None
    nbytes = sum(counts.decode_step_bytes(cfg, s.decode_contexts)
                 for s in steps)
    return 100.0 * nbytes / (seconds * ctx["chips"]
                             * ctx["peaks"]["hbm_bytes_per_s"])

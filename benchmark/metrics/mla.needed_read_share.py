"""How much of what a decode step's latent read brought in it had to: 100 x
``mla.rows_live`` / ``mla.rows_read`` over the window's decode steps, from
the program's device counters — the rows the live slots' positions say a
step attends (a slot at position p: p + 1 a layer) over the read's own count
of the rows it awaited (the kernel: its block copies x the block size; the
XLA lines: the window they gather for every slot).  Near 100 says the kernel
engaged in every step and fetched no more than whole blocks force; a read
that visits blocks it does not need, or a row twice, reads lower.  A program
without the counters gives nothing."""
import moe_counters


def read(run):
    d = moe_counters.delta(run)
    if not d or d.get("mla.rows_read", 0) <= 0 or "mla.rows_live" not in d:
        return None
    return 100.0 * d["mla.rows_live"] / d["mla.rows_read"]

"""What several metric readers share: the client-side sample lists and the
trace's program table.  A reader gets ``run`` (see run.py::main) and returns
a number, or None where it has nothing to read."""

from __future__ import annotations

import stats


def ttft_ms(run) -> list[float]:
    """Stream requests due in the window: due instant -> first token event."""
    if run.mix["route"] != "stream":
        return []
    return [(s.first - s.due) * 1e3 for s in run.counted if s.first is not None]


def tpot_ms(run) -> list[float]:
    if run.mix["route"] != "stream":
        return []
    out = []
    for s in run.counted:
        if s.first is not None and s.done is not None:
            gap = stats.tpot_s(s.first, s.done, s.got)
            if gap is not None:
                out.append(gap * 1e3)
    return out


def latency_ms(run) -> list[float]:
    return [(s.done - s.due) * 1e3 for s in run.counted if s.done is not None]


def late_ms(run) -> list[float]:
    return [(s.sent - s.due) * 1e3 for s in run.counted]


BLOCK_MERGE_S = 0.005


def blocks_of(token_times: list) -> list:
    """A stream's (instant, tokens) stamps with those of one block joined.
    The client stamps every SSE event, and the 16 events of a decode block
    reach it microseconds apart (one write of the engine's); stamps less
    than BLOCK_MERGE_S after the one before belong to the same block, which
    is stamped at its last event.  Separate dispatches lie a decode step or
    more apart (7 ms and up in every cell), so they are never joined."""
    out: list[list] = []
    for t, n in token_times:
        if out and t - out[-1][0] < BLOCK_MERGE_S:
            out[-1][0] = t
            out[-1][1] += n
        else:
            out.append([t, n])
    return out


def tokens_in_window(run) -> float:
    """Tokens of correctly completed streams that reached the client inside
    the window, whenever their request was sent.  Tokens arrive in blocks
    (16 to a stream, every stream of a batch at once); a block is credited
    evenly over the interval since that stream's previous block — the time
    it was made in — so a window's edge cuts a block in proportion and the
    count does not jump by a whole batch's block with the edge's position.
    A stream's first block is credited at its arrival."""
    total = 0.0
    for s in run.samples:
        if not s.ok:
            continue
        prev = None
        for t, n in blocks_of(s.token_times):
            if prev is None:
                total += n if run.w0 <= t < run.w1 else 0
            else:
                overlap = min(t, run.w1) - max(prev, run.w0)
                if overlap > 0:
                    total += n * overlap / (t - prev)
            prev = t
    return total


def rows_in_window(run) -> int:
    return sum(
        s.got for s in run.samples
        if s.ok and s.done is not None and run.w0 <= s.done < run.w1
    )


def stage(run, name: str):
    """Counts the engine recorded in a stage between the two snapshots."""
    return stats.hist_delta(
        run.before.get("stage_hist", {}), run.after.get("stage_hist", {}), name
    )


def programs(run, prefix: str) -> list[dict]:
    """The traced device programs whose host annotation starts with
    ``prefix``: dicts with ``label`` and ``device_s``."""
    if not run.trace:
        return []
    return [p for p in run.trace["programs"] if p["label"].startswith(prefix)]


def decode_step_s(run) -> float | None:
    """Device time of one decode step: total device time of the traced
    decode blocks over the steps they made (k of ``decode_k:k<k>:w<w>``)."""
    blocks = programs(run, "decode_k:")
    if not blocks:
        return None
    steps = sum(int(p["label"].split(":")[1][1:]) for p in blocks)
    return sum(p["device_s"] for p in blocks) / steps


def mean_context_tokens(run) -> float:
    """Tokens a saturated decode step attends to, from the traffic's shapes:
    every slot busy, each at its mean prompt plus half its output."""
    mix, slots = run.mix, int(run.config["graph"]["parameters"]["n_slots"])
    per_slot = (
        run.traffic.mean_of(mix["prompt_len"]) + run.traffic.mean_of(mix["output_len"]) / 2
    )
    return slots * per_slot

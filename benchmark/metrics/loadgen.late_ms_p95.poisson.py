"""How late the generator ran: actual send - due instant, 95th percentile.
(``loadgen.late_ms_p95`` in the cells judged on ``ttft_ms_p50``.)"""
import metriclib as ml


def read(run):
    xs = ml.late_ms(run)
    return run.stats.percentile(xs, 95) if xs else None

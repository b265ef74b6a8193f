"""How late the generator ran: actual send - due instant, 95th percentile."""
import metriclib as ml


def read(run):
    xs = ml.late_ms(run)
    return run.stats.percentile(xs, 95) if xs else None

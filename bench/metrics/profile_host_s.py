"""Mean seconds per job in profile_snn outside the LIF scan: the
``profile`` span less its ``lif_scan`` child (trace expansion, graph and
hypergraph build)."""
import program_spans


def read(ctx: dict):
    return program_spans.host_seconds(ctx, "profile", "profile", "lif_scan")

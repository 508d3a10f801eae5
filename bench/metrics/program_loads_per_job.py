"""Programs compiled or loaded from the persistent compile cache per job
inside the window, counted on the program's spans of its jobs."""
import program_spans


def read(ctx: dict):
    spans = [program_spans.roots(ctx, name) for name in ("profile", "toolchain")]
    if None in spans:
        return None
    loads = sum(r.total("compiles") + r.total("cache_loads")
                for roots in spans for r in roots)
    return loads / len(ctx["jobs"])

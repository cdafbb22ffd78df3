"""Share of the window's box-QP solves that stopped at ``max_iters``
without meeting the KKT tolerance: ``capped`` over ``solves`` (real slots
only), the ``train.fista.*`` counts the window's ``train.wave.solve``
spans carry."""
import program_trace


def read(ctx):
    c = program_trace.fista_counts(ctx)
    if c.get("solves", 0) <= 0:
        return None
    return 100.0 * c["capped"] / c["solves"]

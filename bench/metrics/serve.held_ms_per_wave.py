"""Mean milliseconds a serve wave sat in flight before the host asked for
it: ``held_ms`` (end of the dispatch to the start of ``finish_step``) of
the window's wave records.  ``None`` where the records carry no
``held_ms``, as in an engine that does not record it."""


def read(ctx):
    s = ctx.window.get("serve")
    if not s:
        return None
    held = [w["held_ms"] for w in s.get("waves", ()) if "held_ms" in w]
    if not held:
        return None
    return sum(held) / len(held)

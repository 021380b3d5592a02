"""Mean of the ``active`` attribute over the window's ``decode_step`` spans."""


def read(ctx):
    act = [s.attrs["active"] for s in ctx["spans"]
           if s.name == "decode_step" and s.attrs and "active" in s.attrs]
    return sum(act) / len(act) if act else None

"""The program recorder's complete spans, for the span metric readers."""


def spans(ctx, name: str) -> list[dict]:
    return [e for e in ctx.events if e.get("ph") == "X"
            and e["name"] == name]


def mean_ms(ctx, name: str):
    got = spans(ctx, name)
    if not got:
        return None
    return sum(e["dur"] for e in got) / len(got) / 1e3

"""Embeddings the encoder computed during the window over questions
asked (the tap at SentenceEncoder.encode; the window ingests nothing, so
every text is a question's)."""


def read(ctx):
    asked = len(getattr(ctx, "records", None) or [])
    if not asked:
        return None
    embedded = sum(r[3] for r in ctx.tap.encodes if r[2] == "window")
    return embedded / asked if embedded else None

"""Time of the model's one-pass prefill at set-up: every ``model.prefill``
span that ``launch/serve.py``'s ``fill_cache`` records (one a call, each
closed once its call has finished; the prefill's compile is outside
them), in seconds. Nothing where the program fills no cache that way."""


def read(ctx):
    us = [e["dur"] for e in ctx.spans
          if e.get("ph") == "X" and e["name"] == "model.prefill"]
    return sum(us) * 1e-6 if us else None

"""service/engine.py: mean `CeremonyOutcome.seconds`, the convoy's wall time
over its width as the scheduler books it, in milliseconds."""


def read(ctx: dict) -> float | None:
    engine = [r["engine_s"] for r in ctx["records"] if r.get("engine_s")]
    return sum(engine) / len(engine) * 1000.0 if engine else None

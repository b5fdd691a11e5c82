"""Reductions of the trace that several per-layer metrics share."""


def program_runs(record, needle):
    """Device milliseconds, one per execution, of the traced XLA program
    whose name holds `needle` and that took most time in all."""
    programs = (record.get("trace") or {}).get("programs") or {}
    named = [v for k, v in programs.items() if needle in k]
    return max(named, key=sum) if named else None

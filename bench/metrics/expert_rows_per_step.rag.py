"""Rows the held experts received per engine step in the RAG chat cell,
summed over the step's programs and layers, as the engine counts them
(model step); beside it the busiest held expert's rows in one layer."""
from bench import program_trace


def read(ctx):
    rows = [a for a in program_trace.step_counts(ctx) if "expert_rows" in a]
    if not rows:
        return None
    return {"value": sum(a["expert_rows"] for a in rows) / len(rows),
            "max_per_expert": max(a["expert_rows_max"] for a in rows),
            "steps": len(rows)}

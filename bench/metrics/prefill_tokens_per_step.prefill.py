"""prefill_tokens_per_step.prefill: prompt tokens prefilled per
``Scheduler.step`` call in the traced window (the scheduler's
``prefill_tokens_computed`` counter over the benchmark's count of step
spans); chunked prefill advances at most one chunk a step."""


def read(x):
    if not x.get("steps"):
        return None
    return x["prefill_tokens"] / x["steps"]

"""Postings the planner sized each batch for, per query: the sum over the
window's batches of ``RetrievalPlan.sum_df`` (the df of the batch's
distinct tokens) over the queries answered."""


def read(ctx):
    if not ctx.batches:
        return None
    return sum(b.plan_sum_df for b in ctx.batches) / ctx.queries

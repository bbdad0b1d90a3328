"""sweep.fifo_blocked_share (%): of the valid non-timer pool rows the traced jobs' live lanes held at their segment boundaries (`sweep.fifo_pending_rows`), those that were not their (sender, receiver) channel's head (100 less `sweep.fifo_head_rows` over them; both sampled by the continuous driver beside the pool-peak reduce, only while spans are live): how much of the pool the FIFO discipline holds back from the scheduler's choice. It describes the traffic (a long resend burst on one link reads high) and moves nothing, so `moves` and `better` are what the schema needs. An app whose channels keep no order has neither count, nor has the parent of the PR that brought them: none is given."""

from lib.stage_share import SWEEP_ROOT, count_ratio


def read(obs):
    heads = count_ratio(
        "sweep.fifo_head_rows", "sweep.fifo_pending_rows", SWEEP_ROOT
    )
    return None if heads is None else 100.0 - heads

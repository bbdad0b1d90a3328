"""sweep.discarded_row_share (%): of the rows the traced jobs' retired lanes put in their pools (`sweep.rows_inserted`), those the network lost undelivered at the scheduler's choice (`sweep.net.discarded`: each lane's `drops`, summed at the retire while spans are live, only for a kernel built for datagram channels, `DSLApp.channels`): rows that paid the insert and reached no handler. About `drop_weight` times the share of rows that are actors' messages, less what `max_drops` cuts. It describes the traffic and moves nothing, so `moves` and `better` are what the schema needs. A program whose network loses nothing on its own keeps no such count and gives none."""

from lib.stage_share import SWEEP_ROOT, tables


def read(obs):
    found = tables()
    if found is None:
        return None
    totals, counts = found
    if (
        SWEEP_ROOT not in totals or "sweep.net.discarded" not in counts
        or not counts.get("sweep.rows_inserted")
    ):
        return None
    return 100.0 * counts["sweep.net.discarded"] / counts["sweep.rows_inserted"]

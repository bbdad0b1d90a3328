"""sweep.kept_delivery_share (%): of the deliveries the traced jobs' retired lanes made (`sweep.net.delivered`: each lane's `deliveries`, summed at the retire while spans are live, only for a kernel built for datagram channels, `DSLApp.channels`), those that left their message pending to be delivered again (`sweep.net.kept`: each lane's `dups`): how often the datagram discipline engages. About `dup_weight` times the share of deliveries that are actors' messages (a timer and a client's send are never kept), less what `max_dups` cuts. It describes the traffic, as `sweep.fault_op_share` does, and moves nothing, so `moves` and `better` are what the schema needs. A program whose network repeats nothing (any other app, or the parent of the PR that brought the discipline) keeps no such counts and gives none."""

from lib.stage_share import SWEEP_ROOT, count_ratio


def read(obs):
    return count_ratio("sweep.net.kept", "sweep.net.delivered", SWEEP_ROOT)

"""sweep.outbox_fill_share (%): the rows the traced jobs' retired lanes put in their pools (`sweep.rows_inserted`: each lane's `seq_counter`, which every insert advances by its row count, pulled at the retire while spans are live) over the outbox rows their deliveries carried through the insert (`sweep.outbox_rows`: deliveries x `max_outbox`): how much of the insert's [K, P] pass is over real rows. External sends and a start's initial messages are rows too, so a protocol of one-row outboxes can read a little over 100. A program that keeps no such counts gives none."""

from lib.stage_share import SWEEP_ROOT, count_ratio


def read(obs):
    return count_ratio("sweep.rows_inserted", "sweep.outbox_rows", SWEEP_ROOT)

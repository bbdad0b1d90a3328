"""sweep.preempts_per_schedule (1/schedule): the preemptions the traced jobs' retired lanes' leaders went through (`sweep.app.preempts`: the app's progress count of that name, `DSLApp.progress`, a ghost word each Multi-Paxos leader counts, summed at the retire while spans are live) over the lanes retired (`sweep.retired`): how hard the leaders duel. Each preemption is followed by a scout and, once it is adopted, by one burst of 5 P2A rows for every slot the leader holds: the inserts that take the full [K, P] pass, so it sets how many bursts a schedule holds. It describes the traffic and moves nothing, so `moves` and `better` are what the schema needs. A program, or an app, that keeps no such count gives none."""

from lib.app_counts import app_ratio


def read(obs):
    return app_ratio("preempts", "sweep.retired")

"""sweep.truncated_per_schedule (records/schedule): the log entries the traced jobs' retired lanes' followers dropped (`sweep.app.truncated`: the app's progress count of that name, `DSLApp.progress`, the sum over a lane's brokers and partitions of the durable ghost word TRUNCATED, counted where a follower cut its log: to the offset its leader's epoch cache named, or under `truncate_to_hw` to its own high watermark; taken at the retire while spans are live) over the lanes retired (`sweep.retired`): how much of the truncation rule a schedule exercises, the rule KIP-101 replaced. It describes the traffic and moves nothing, so `moves` and `better` are what the schema needs. A program, or an app, that keeps no such count gives none."""

from lib.app_counts import app_ratio


def read(obs):
    return app_ratio("truncated", "sweep.retired")

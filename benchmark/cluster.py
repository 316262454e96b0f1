"""The cell's servers, started in this process (a chip belongs to one
process) and reached only over HTTP.  Which servers, and how, is the
configuration file's to say."""
import time


class Servers:
    def __init__(self, config: dict):
        from presto_tpu.worker import WorkerServer
        spec = config["servers"]
        # every server with the server's default ExecutionConfig
        self.coordinator = WorkerServer(coordinator=True,
                                        **spec.get("coordinator", {}))
        self.workers = [
            WorkerServer(discovery_uri=self.coordinator.uri,
                         **spec.get("worker", {"announce_interval_s": 0.1}))
            for _ in range(spec.get("workers", 0))]
        deadline = time.time() + 30
        while len(self.coordinator.worker_uris()) < len(self.workers):
            if time.time() > deadline:
                raise RuntimeError("a worker never announced itself")
            time.sleep(0.05)
        self.uri = self.coordinator.uri
        self.schema = f"sf{config['scale_factor']:g}"
        self.catalog = config["catalog"]

    def client(self, source: str = "bench"):
        from presto_tpu.client import StatementClient
        return StatementClient(self.uri, schema=self.schema,
                               catalog=self.catalog, source=source,
                               timeout_s=900.0)

    def close(self):
        for w in self.workers:
            w.close()
        self.coordinator.close()

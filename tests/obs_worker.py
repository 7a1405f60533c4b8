"""Worker harness for the obs chaos post-mortem test (tests/test_obs.py).

One elastic worker process with the full telemetry stack on: span tracing
into the registry + a JSONL event log + the crash flight recorder, all
over the SAME storage directory the checkpoints (and the supervisor) use.
On its first attempt it SIGKILLs itself mid-epoch via
``FaultInjector(kill_mode="process")`` — the real preemption shape — and
on the respawn it rejoins the next membership generation, finishes the
run, scrapes its own ``/metrics`` endpoint and drops the scrape into the
store for the test to assert on.

argv: <store_dir> <worker_id> <attempt> <num_epochs> <kill_at_step>
exit: 0 done · 17 ELASTIC_RESTART_EXIT · killed by SIGKILL on attempt 1
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=2")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import urllib.request  # noqa: E402

import numpy as np  # noqa: E402

from deeplearning4j_tpu import obs  # noqa: E402
from deeplearning4j_tpu.checkpoint import CheckpointManager  # noqa: E402
from deeplearning4j_tpu.checkpoint.faults import FaultInjector  # noqa: E402
from deeplearning4j_tpu.checkpoint.storage import LocalFSBackend  # noqa: E402
from deeplearning4j_tpu.checkpoint.supervisor import (  # noqa: E402
    ELASTIC_RESTART_EXIT)
from deeplearning4j_tpu.datasets.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.nn.conf import (InputType,  # noqa: E402
                                        NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,  # noqa: E402
                                               OutputLayer)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.optimize.updaters import Sgd  # noqa: E402
from deeplearning4j_tpu.parallel.elastic import (ElasticWorker,  # noqa: E402
                                                 ElasticRestartRequired)
from deeplearning4j_tpu.storage import InMemoryStatsStorage  # noqa: E402
from deeplearning4j_tpu.ui import UIServer  # noqa: E402


def model_factory():
    conf = (NeuralNetConfiguration.builder()
            .seed(7).updater(Sgd(learning_rate=0.05)).weight_init("xavier")
            .list()
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=3, loss="mcxent"))
            .set_input_type(InputType.feed_forward(8))
            .build())
    return MultiLayerNetwork(conf)


def make_data(batches=4, batch=32):
    rng = np.random.default_rng(0)
    return [DataSet(rng.standard_normal((batch, 8)).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rng.integers(0, 3, batch)])
            for _ in range(batches)]


def main() -> int:
    store_dir, worker_id = sys.argv[1], sys.argv[2]
    attempt, num_epochs = int(sys.argv[3]), int(sys.argv[4])
    kill_at_step = int(sys.argv[5])
    backend = LocalFSBackend(store_dir)

    # the full telemetry stack, all over the shared store
    reg = obs.get_registry()
    obs.configure_tracer(enabled=True, registry=reg)
    obs.install_flight_recorder(store=backend, worker_id=worker_id)
    elog = obs.EventLog(backend, name=f"events-{worker_id}-a{attempt}.jsonl",
                        flush_every=1)
    obs.get_tracer().add_sink(elog)

    cm = CheckpointManager(storage=backend, sharded=True, async_write=False)

    def on_generation(model, membership, rank, world):
        if attempt == 1:
            model.set_listeners(FaultInjector(kill_at_step=kill_at_step,
                                              kill_mode="process"))

    worker = ElasticWorker(store=backend, worker_id=worker_id,
                           checkpoint_manager=cm, num_workers=1,
                           lease_ttl_s=3.0, join_timeout_s=60.0,
                           poll_s=0.05, collective_timeout_s=60.0,
                           on_generation=on_generation)
    try:
        summary = worker.run(model_factory, make_data(),
                             num_epochs=num_epochs)
    except ElasticRestartRequired:
        return ELASTIC_RESTART_EXIT
    if not summary.completed:
        return 3

    # the run's own Prometheus scrape, through the REAL /metrics endpoint,
    # parked in the store for the supervising test to assert on
    srv = UIServer(port=0).attach(InMemoryStatsStorage())
    try:
        scrape = urllib.request.urlopen(
            srv.address.rstrip("/") + "/metrics", timeout=10).read()
    finally:
        srv.stop()
    backend.put(f"prom-{worker_id}-a{attempt}.txt", scrape)
    elog.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())

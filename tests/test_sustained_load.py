"""Sustained-load invariant harness (VERDICT r3 #6): a rate-paced
linked-list workload against a real-process cluster while compactions,
a kill -9, a restart and a tablet split churn underneath — then a full
verification walk plus ysck and cross-replica checksums.

Scaled for CI (~45 s of load); YBTPU_LOAD_SECONDS=300 runs the full
5-minute soak the reference's linked_list-test targets.
ref: src/yb/integration-tests/linked_list-test.cc,
src/yb/util/load_generator.h.
"""

import io
import os
import time

import pytest

from yugabyte_tpu.integration.external_mini_cluster import (
    ExternalMiniCluster)
from yugabyte_tpu.integration.load_generator import (
    LINKED_LIST_SCHEMA, LinkedListLoadGenerator)
from yugabyte_tpu.tools import ysck


@pytest.mark.slow
def test_linked_list_under_churn(tmp_path):
    seconds = float(os.environ.get("YBTPU_LOAD_SECONDS", 45))
    c = ExternalMiniCluster(str(tmp_path / "cluster"), num_tservers=3,
                            rf=3).start()
    try:
        c.wait_tservers_alive(3)
        client = c.new_client()
        client.create_namespace("load")
        # small memstore via cluster flags would need restarts; default
        # flushes still occur from the volume of writes over the run
        table = client.create_table("load", "chains", LINKED_LIST_SCHEMA,
                                    num_tablets=4)
        # deflake: writers must not race the fresh tablets' first
        # elections (the known create-then-write leadership flake)
        c.wait_table_leaders(client, table.table_id)

        gen = LinkedListLoadGenerator(client, table, n_chains=4,
                                      ops_per_sec=120.0).start()
        third = seconds / 3.0
        time.sleep(third)

        # churn 1: kill -9 a tserver mid-load, writers keep going
        c.tservers[1].kill9()
        time.sleep(third / 2)
        # churn 2: restart it (remote bootstrap / catch-up underneath)
        c.tservers[1].start()
        c.wait_tservers_alive(3)
        time.sleep(third / 2)

        # churn 3: split one tablet of the loaded table mid-writes
        locs = client._master_call("get_table_locations",
                                   table_id=table.table_id)
        client._master_call("split_tablet",
                            tablet_id=locs[0]["tablet_id"])
        time.sleep(third)

        report = gen.stop()
        assert report.written_acked > seconds * 40, (
            f"load too slow to be meaningful: {report}")

        # full verification walk: no lost, no phantom, no broken chains
        counters = gen.verify(client)
        assert counters["present"] >= report.written_acked

        # cross-replica agreement + cluster health
        c.verify_replica_checksums(client, table)
        buf = io.StringIO()
        problems = ysck.check_cluster([c.master.address], out=buf)
        assert problems == 0, f"ysck found problems:\n{buf.getvalue()}"
        client.close()
    finally:
        c.shutdown()

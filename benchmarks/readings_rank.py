"""`readings.py` for the ranking cell: the same seeds x variants, with
`faults_rank.FAULTS` (the three faults of the tree step and
`pairs_dropped`) as the list of faults it walks.

    python benchmarks/readings_rank.py --workload msltr-rank-1chip \\
        --seeds 11,12 --fault-seeds 1 --bins-seeds 1 --fresh-data \\
        --out chiprun_out/readings_rank.jsonl
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import faults  # noqa: E402
import faults_rank  # noqa: E402
import readings  # noqa: E402

if __name__ == "__main__":
    faults.FAULTS = faults_rank.FAULTS
    sys.exit(readings.main())

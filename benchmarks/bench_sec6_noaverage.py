"""Section 6 analog: SGP-SlowMo-noaverage — remove the periodic exact average.

Paper claims: noaverage performs close to full SlowMo-SGP (within noise on
ImageNet, slightly worse on WMT) at the base algorithm's communication cost —
i.e. the slow momentum UPDATE, not the buffer synchronization, carries the
gain."""
from __future__ import annotations

from . import common

ALGOS = ["sgp", "sgp+slowmo", "sgp+slowmo-noaverage"]


def main():
    print("# Sec 6 analog: noaverage variant (tau=12, beta=0.6)")
    print("algorithm,final_train_loss,eval_loss")
    for name in ALGOS:
        r = common.run_algorithm(name, common.preset_cfg(name))
        print(f"{name},{r.final_loss:.4f},{r.eval_loss:.4f}")


if __name__ == "__main__":
    main()

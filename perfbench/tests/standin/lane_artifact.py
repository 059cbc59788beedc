"""The stand-in lane's artifact: the seed, which is all its weights are
made from (``byte_lm.build_params``), and the configuration's sizes.  The arguments are those every
artifact child is started with."""

import argparse
import json
import os
import sys

p = argparse.ArgumentParser()
p.add_argument("--config", required=True)
p.add_argument("--seed", type=int, required=True)
p.add_argument("--out", required=True)
p.add_argument("--module-cache")
p.add_argument("--platform")
args = p.parse_args()
with open(args.config) as f:
    config = json.load(f)
directory = os.path.join(args.out, config["served_name"])
os.makedirs(directory)
with open(os.path.join(directory, "lane.json"), "w") as f:
    json.dump({"seed": args.seed, "config": config}, f)
sys.exit(0)

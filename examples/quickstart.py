"""Quickstart: one retrospective Retrieval query on a zero-streaming
camera, end to end, in ~2 minutes on CPU.

    PYTHONPATH=src python examples/quickstart.py

What happens (the paper's Fig. 3 workflow):
  1. A synthetic 1-hour scene ("Banff", buses at a crossing) is captured
     to camera-local storage — nothing is streamed.
  2. At capture time the camera runs its best detector on 1-in-30 frames
     (sparse-but-sure landmarks).
  3. A query arrives: "retrieve all frames containing a bus". The cloud
     pulls landmark thumbnails, learns the spatial/temporal skew, breeds
     + trains cheap operators, and pushes them to the camera.
  4. The camera ranks frames in multiple passes (operators upgraded
     mid-query); positives stream back ordered-best-first.
"""
import sys
import time

sys.path.insert(0, "src")

from repro.core import landmarks as lm
from repro.core.hardware import YOLO_V3
from repro.core.query import Query, make_env
from repro.core.ranking import RetrievalExecutor
from repro.core.video import Video, corpus
from repro.launch import compile_cache


def main():
    compile_cache.enable()
    t0 = time.time()
    print("== 1. capture (zero streaming) ==")
    video = Video(corpus(hours=1.0)["Banff"])
    print(f"   scene=Banff frames={video.spec.num_frames} "
          f"(stored on camera; 0 bytes uploaded)")

    print("== 2. capture-time landmarks (1-in-30, best detector) ==")
    store = lm.build_landmarks(video, 30, YOLO_V3)
    print(f"   {len(store.landmarks)} landmarks with {YOLO_V3.name} labels")

    print("== 3. query: retrieve frames containing 'bus' ==")
    env = make_env(video, Query("retrieval", "bus"), store)
    print(f"   queried range: {env.n_frames} frames, "
          f"{env.n_positives} true positives")

    ex = RetrievalExecutor(env, full_family=False)
    prog = ex.run()

    print("== 4. results (online: partial results stream in) ==")
    for frac in (0.25, 0.5, 0.9, 0.99):
        t = prog.time_to(frac)
        if t:
            print(f"   {frac:>4.0%} of positives after {t:8.1f} simulated s")
    video_s = env.n_frames / video.spec.fps
    print(f"   full query: {prog.done_t:.0f} s simulated "
          f"= {video_s / prog.done_t:.0f}x video realtime")
    print(f"   network: {prog.bytes_up / 1e6:.1f} MB uploaded "
          f"(all-streaming would be {env.n_frames * env.net.frame_bytes / 1e6:.0f} MB)")
    print(f"   operators used: {[n for _, n in prog.op_switches]}")
    print(f"(host wall time {time.time() - t0:.0f}s)")


if __name__ == "__main__":
    main()

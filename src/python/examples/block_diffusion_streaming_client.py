#!/usr/bin/env python3
"""Stream an answer from a block-diffusion model over the decoupled gRPC
stream: one response a FINISHED BLOCK, carrying its tokens in position
order with the pass that unmasked each (``TOKEN``, ``LOGPROB``,
``POSITION``, ``UNMASK_PASS``).

``denoising_steps`` is the caller's trade of quality for speed: at the
block length (the default) a pass unmasks one token, at half of it two,
so the same answer takes about half the passes.  The server is a
``LlamaGenerateModel`` whose configuration has ``block_len`` > 0
(``tpuserver.models.llama.tiny_sdar()`` in the test) on the
continuous-batching scheduler."""

import argparse
import sys

import numpy as np

import tritonclient.grpc as grpcclient


def generate_blocks(client, model, prompt, max_tokens, denoising_steps):
    p_in = grpcclient.InferInput("PROMPT_IDS", [len(prompt)], "INT32")
    p_in.set_data_from_numpy(np.asarray(prompt, dtype=np.int32))
    m_in = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
    m_in.set_data_from_numpy(np.array([max_tokens], dtype=np.int32))
    blocks = []
    # resume=False: a block configuration does not replay a dropped
    # stream yet (the server refuses resume_generation_id by name)
    for result in client.generate_stream(
            model, [p_in, m_in], resume=False,
            parameters={"denoising_steps": denoising_steps}):
        block = {name: result.as_numpy(name).tolist() for name in (
            "TOKEN", "POSITION", "UNMASK_PASS")}
        blocks.append(block)
        print("block at {}: tokens {} unmasked in passes {}".format(
            block["POSITION"][0], block["TOKEN"], block["UNMASK_PASS"]),
            flush=True)
    return blocks


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("-u", "--url", default="localhost:8001")
    parser.add_argument("-m", "--model", default="llama_generate")
    parser.add_argument("-n", "--max-tokens", type=int, default=12)
    parser.add_argument("-b", "--block-length", type=int, default=4)
    args = parser.parse_args()

    client = grpcclient.InferenceServerClient(args.url)
    try:
        prompt = [1, 5, 9, 13, 2, 6]
        for steps in (args.block_length, args.block_length // 2):
            blocks = generate_blocks(
                client, args.model, prompt, args.max_tokens, steps)
            at = [p for b in blocks for p in b["POSITION"]]
            passes = max(p for b in blocks for p in b["UNMASK_PASS"]) + 1
            print("denoising_steps {}: {} tokens in {} blocks, at most {} "
                  "denoise passes a block".format(
                      steps, len(at), len(blocks), passes))
            if at != list(range(len(prompt), len(prompt) + args.max_tokens)):
                print("FAILED: positions {}".format(at))
                sys.exit(1)
            if passes > steps:
                print("FAILED: {} passes a block at denoising_steps "
                      "{}".format(passes, steps))
                sys.exit(1)
    finally:
        client.close()
    print("PASS: block diffusion streaming")


if __name__ == "__main__":
    main()

// One frame into one slot of a staging lane: the layout FrameRing holds
// and the device step reads (pushcdn_tpu/parallel/frames.py). Shared by
// the batch packer (framing.cpp, pushcdn_pack_frames) and the receive
// chunk stager (pydecode.cpp, pushcdn_stage_chunk_py), so that a frame
// packed by either lands byte for byte the same.

#pragma once

#include <cstdint>
#include <cstring>

// Slot `i` of a lane of `frame_bytes`-wide slots: the payload, zero
// padding to the slot's width, and the metadata columns. `mask` holds
// `topic_words` u32 words (word w = topics 32w..32w+31).
static inline void pushcdn_pack_slot(
    uint8_t* frames, int32_t* kinds, int32_t* lens, uint32_t* tmasks,
    int32_t* dests, uint8_t* valid, int64_t i, int32_t frame_bytes,
    int32_t topic_words, const uint8_t* payload, int32_t len, int32_t kind,
    const uint32_t* mask, int32_t dest) {
  uint8_t* slot = frames + i * frame_bytes;
  std::memcpy(slot, payload, (size_t)len);
  if (len < frame_bytes)
    std::memset(slot + len, 0, (size_t)(frame_bytes - len));
  kinds[i] = kind;
  lens[i] = len;
  std::memcpy(tmasks + i * topic_words, mask,
              (size_t)topic_words * sizeof(uint32_t));
  dests[i] = dest;
  valid[i] = 1;
}
